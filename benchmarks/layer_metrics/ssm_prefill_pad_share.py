"""Layer: model layers. Of the positions the window's prefills ran their scan
over, the share that was padding: (bucket - tokens) over bucket, summed over
the window's `dl4j/engine/prefill.prepare` spans (`bucket`, `tokens`), in
percent. A state-space layer's chunked scan runs over the whole bucket; the
padding's steps carry the state unchanged and cost what real ones cost."""
from harness import spanlog

PREPARE = "dl4j/engine/prefill.prepare"


def compute(env):
    w = spanlog.serve_window(spanlog.records(), env.facts)
    if not w:
        return None
    spans = [d["attrs"] for a in w.admits for d in w.idx.descendants(a)
             if d["name"] == PREPARE and "bucket" in d["attrs"]]
    bucket = sum(a["bucket"] for a in spans)
    if not bucket:
        return None
    return 100.0 * (bucket - sum(a["tokens"] for a in spans)) / bucket
