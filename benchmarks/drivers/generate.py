"""Driver `generate`: a closed loop of HTTP clients on POST
/v1/models/<name>/generate of an InferenceServer started in this process.

Traffic parameters (the cell file's "traffic"): clients, prompt_tokens and
max_tokens (each {"dist": "loguniform"|"uniform", "lo", "hi"}), temperature,
ramp_seconds, request_timeout_seconds. Every seed sends the same set of
(prompt length, max_tokens) pairs, the quantiles of the two distributions
paired by a fixed shuffle, in an order drawn from the seed; token ids are
uniform over the published vocabulary, from the seed. A client sends its next
request when the last reply has arrived: the reply is not streamed, so a
caller waits for it.

The clients run `ramp_seconds` before the window opens, so that the batch is
full, and go on sending after it closes until every request sent inside it has
been answered: those requests are served under the window's load to their end.
Latency is taken over every request sent inside the window. Tokens per second
are real tokens over real time: a client's requests follow one another with no
gap, so the tokens of its requests sent inside the window, over the time from
the first one's send to the last one's reply, is that client's rate, and the
clients' rates are added. (Counting whole replies where they arrive inside
fixed ends moves some 80 tokens a client across each end: a spread of 3-4.5%
on 45 s, PERF.md section 2.)

`correct`: once the clients have stopped, a sample of the finished requests
(the longest, and others drawn from the seed) is fed again, prompt and served
tokens, through the window's own prefill and tick executables over its own
arena, with every row of the largest batch bucket in use, and the logits are
kept. When the server is stopped and freed the reference runs over each
sampled sequence. Compared: the widest gap by which a served token's logit
lies below the best of the reference in true float32, and the error of the
executables' logits against the reference in the arithmetic that the
configuration states (`precision.reference`).
"""
from __future__ import annotations

import gc
import json
import re
import threading
import time
import urllib.request

import numpy as np

POOL = 64           # pairs in one cycle of sizes; every cycle holds them all


def size_pool(traffic: dict) -> list:
    """The fixed set of (prompt tokens, max_tokens): POOL quantiles of each
    distribution, paired by a shuffle that no seed changes."""
    def quantiles(spec):
        q = (np.arange(POOL) + 0.5) / POOL
        lo, hi = float(spec["lo"]), float(spec["hi"])
        if spec["dist"] == "loguniform":
            return np.rint(np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo))))
        if spec["dist"] == "uniform":
            return np.rint(lo + q * (hi - lo))
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    prompts = quantiles(traffic["prompt_tokens"]).astype(int)
    outs = quantiles(traffic["max_tokens"]).astype(int)
    np.random.default_rng(0).shuffle(outs)
    return list(zip(prompts.tolist(), outs.tolist()))


class Requests:
    """The stream of requests, shared by the clients: request k takes the
    k-th pair of the seed's order (a fresh shuffle of the pool each cycle)."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.pool = size_pool(traffic)
        self.vocab = int(config["vocab_size"])
        self.temperature = float(traffic["temperature"])
        self.order_rng = np.random.default_rng([int(seed), 2])
        self.seed = int(seed)
        self.lock = threading.Lock()
        self.k = 0
        self.order = []

    def next(self) -> dict:
        with self.lock:
            if not self.order:
                self.order = self.order_rng.permutation(len(self.pool)).tolist()
            n, g = self.pool[self.order.pop()]
            k = self.k
            self.k += 1
        tokens = np.random.default_rng([self.seed, 3, k]).integers(
            0, self.vocab, n).tolist()
        return {"k": k, "prompt": tokens, "max_tokens": g,
                "temperature": self.temperature}


def _post(url: str, body: dict, timeout: float) -> dict:
    req = urllib.request.Request(
        url, json.dumps(body).encode(), {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _client(c, url, requests, timeout, stopped, log):
    import jax
    while not stopped.is_set():
        r = requests.next()
        rec = {"c": c, "k": r["k"], "prompt": r["prompt"],
               "max_tokens": r["max_tokens"], "sent": time.perf_counter(),
               "ok": False, "tokens": []}
        try:
            with jax.profiler.TraceAnnotation("bench/request"):
                reply = _post(url, {"prompt": r["prompt"],
                                    "max_tokens": r["max_tokens"],
                                    "temperature": r["temperature"]}, timeout)
            rec["tokens"] = [int(t) for t in reply["tokens"]]
            rec["ok"] = (reply.get("finish_reason") == "length"
                         and len(rec["tokens"]) == r["max_tokens"])
            if not rec["ok"]:
                rec["error"] = f"bad reply: {str(reply)[:200]}"
        except Exception as e:     # noqa: BLE001 - counted as a failed request
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["done"] = time.perf_counter()
        log.append(rec)


def _scrape(base: str, model: str) -> dict:
    """The decode plane's counters, from /metrics."""
    with urllib.request.urlopen(f"{base}/metrics", timeout=60) as resp:
        text = resp.read().decode()
    out = {}
    for phase in ("prefill", "decode"):
        for part in ("sum", "count"):
            m = re.search(
                rf'dl4j_decode_phase_seconds_{part}\{{model="{re.escape(model)}"'
                rf',phase="{phase}"\}} (\S+)', text)
            out[f"{phase}_{part}"] = float(m.group(1)) if m else 0.0
    m = re.search(rf'dl4j_decode_tokens_total\{{model="{re.escape(model)}"\}} (\S+)',
                  text)
    out["tokens_total"] = float(m.group(1)) if m else 0.0
    return out


def _sample_for_check(finished: list, seed: int, count: int, rows: int):
    """(sample, fillers): the longest finished request and `count - 1` more
    drawn from the seed, which are compared; and the finished requests with
    most served tokens besides, up to `rows` in all, which keep the other rows
    of the batch in use while the sample is fed again."""
    if not finished:
        return [], []
    by_len = sorted(finished, key=lambda r: (len(r["prompt"]) + len(r["tokens"]),
                                             r["k"]))
    longest, rest = by_len[-1], by_len[:-1]
    rng = np.random.default_rng([int(seed), 4])
    pick = set(rng.choice(len(rest), size=min(count - 1, len(rest)),
                          replace=False).tolist())
    sample = [longest] + [rest[i] for i in sorted(pick)]
    others = sorted((r for i, r in enumerate(rest) if i not in pick),
                    key=lambda r: (-len(r["tokens"]), r["k"]))
    return sample, others[:max(0, rows - len(sample))]


def feed_again(engine, version, pool, rows: list, keep: int) -> list:
    """Prompts and served tokens of `rows` through the engine's own prefill
    and tick executables (the compiled objects the window used, its arena, the
    bucket that holds all rows), a row leaving when its tokens are used up.
    Returns, for each of the first `keep` rows, the logits [served, V] that
    chose its served tokens."""
    bucket = engine.decode_bucket_for(len(rows))
    tables, out = [], [[] for _ in rows[:keep]]
    for i, r in enumerate(rows):
        blocks = pool.alloc(engine.spec.blocks_for(len(r["prompt"])
                                                   + len(r["tokens"])))
        tables.append(blocks)
        z = engine.run_prefill(version, pool, r["prompt"], blocks)
        if i < keep:
            out[i].append(np.array(z, np.float32))
    step = 0
    while True:
        live = [i for i, r in enumerate(rows) if len(r["tokens"]) > step + 1]
        if not live:
            break
        z = engine.run_tick(
            version, pool, [rows[i]["tokens"][step] for i in live],
            [len(rows[i]["prompt"]) + step for i in live],
            [tables[i] for i in live], bucket=bucket)
        for j, i in enumerate(live):
            if i < keep:
                out[i].append(np.array(z[j], np.float32))
        step += 1
    for blocks in tables:
        pool.release(blocks)
    return [np.stack(z) for z in out]


def check_served(ctx, sample: list, program_logits: list) -> dict:
    """The reference once over each sampled prompt with its served tokens, in
    true float32 for the served tokens' gap and in the arithmetic that the
    configuration states for the executables' logits."""
    ref, config, cmp_ = ctx.reference, ctx.config, ctx.compare
    stated = config["precision"]["reference"]
    params = ref.init_params(config, ctx.seed)
    served, positions, stated_logits = 0.0, 0, []
    for r in sample:
        seq, n, g = r["prompt"] + r["tokens"], len(r["prompt"]), len(r["tokens"])
        z = np.asarray(ref.served_logits(config, params, seq, n, g))
        served = max(served, cmp_.served_gap(z, r["tokens"]))
        stated_logits.append(np.asarray(
            ref.served_logits(config, params, seq, n, g, stated)))
        positions += g
    return {"served": served, "positions": positions,
            "logit_err": cmp_.logit_rel_err(program_logits, stated_logits)}


def client_rates(sent: list, clients: int) -> list:
    """For each client with a request sent inside the window: its span (first
    send to last reply, its requests follow one another with no gap) and the
    prompt and served lengths of its answered requests in it."""
    out = []
    for c in range(clients):
        mine = sorted((r for r in sent if r["c"] == c), key=lambda r: r["sent"])
        if mine:
            ok = [r for r in mine if r["ok"]]
            out.append({"span_s": mine[-1]["done"] - mine[0]["sent"],
                        "prompt_lens": [len(r["prompt"]) for r in ok],
                        "generated": [len(r["tokens"]) for r in ok]})
    return out


def run(ctx) -> dict:
    import jax

    from deeplearning4j_tpu.serving import InferenceServer, ModelRegistry

    cell, config = ctx.cell, ctx.config
    traffic, serve, check = cell["traffic"], cell["serve"], cell["check"]
    name = config["name"]
    prec = config["precision"]
    clients = int(traffic["clients"])

    model = ctx.model.build(config, ctx.seed, ctx.reference, train=False)
    registry = ModelRegistry()
    registry.register(name, model, precision=prec["registry"],
                      buckets=tuple(serve["registry_buckets"]))
    srv = InferenceServer(registry).start()
    log, threads, sample, program_logits = [], [], [], []
    try:
        sched = srv.enable_generation(
            name, kv_dtype=prec["kv_dtype"],
            decode_buckets=tuple(serve["decode_buckets"]),
            prompt_buckets=tuple(serve["prompt_buckets"]))
        v = registry.get(name)
        for tb in sched.engine.prompt_buckets:
            sched.engine.prefill_exec(v, tb)
        for b in sched.engine.decode_buckets:
            sched.engine.decode_exec(v, b)
        base = f"http://{srv.host}:{srv.port}"
        url = f"{base}/v1/models/{name}/generate"
        requests = Requests(config, traffic, ctx.seed)
        timeout = float(traffic["request_timeout_seconds"])
        stopped = threading.Event()
        threads = [threading.Thread(
            target=_client, args=(c, url, requests, timeout, stopped, log),
            name=f"bench-client-{c}", daemon=True) for c in range(clients)]
        for t in threads:
            t.start()
        time.sleep(float(traffic["ramp_seconds"]))

        compiles = ctx.compiles.count
        before = _scrape(base, name)
        t_open = time.perf_counter()
        time.sleep(max(0.0, t_open + ctx.seconds - time.perf_counter()))
        t_close = time.perf_counter()
        after = _scrape(base, name)
        window_compiles = ctx.compiles.count - compiles

        trace_dir = None
        if ctx.trace:
            trace_dir = ctx.trace_dir()
            jax.profiler.start_trace(trace_dir)
            try:
                with jax.profiler.TraceAnnotation("bench/window"):
                    time.sleep(float(cell["trace_seconds"]))
            finally:
                jax.profiler.stop_trace()
        # the load stays on until every client's request that was in flight
        # at the close has been answered (a minute past it if need be)
        while (len({r["c"] for r in list(log) if r["done"] >= t_close}) < clients
               and time.perf_counter() < t_close + timeout + 60.0):
            time.sleep(0.05)
        stopped.set()
        for t in threads:
            t.join(timeout + 60.0)
        hung = [t.name for t in threads if t.is_alive()]

        if not hung:
            sample, fillers = _sample_for_check(
                [r for r in log if r["ok"]], ctx.seed,
                int(check["sample_requests"]), clients)
            t0 = time.perf_counter()
            program_logits = feed_again(sched.engine, v, sched.pool,
                                        sample + fillers, len(sample))
            print(f"[generate] fed again {len(sample)} + {len(fillers)} "
                  f"requests in {time.perf_counter() - t0:.1f}s", flush=True)
    finally:
        stop_err = None
        try:
            srv.stop()
        except Exception as e:      # noqa: BLE001 - reported with the result
            stop_err = f"{type(e).__name__}: {e}"
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith(("dl4j-serving", "dl4j-decode"))]

    window_s = t_close - t_open
    sent = [r for r in log if t_open <= r["sent"] < t_close]
    failed = [r for r in sent if not r["ok"]]
    latency = [(r["done"] - r["sent"]) * 1e3 if r["ok"] else timeout * 1e3
               for r in sent]
    rates = client_rates(sent, clients)
    tokens_per_s = sum(sum(c["generated"]) / c["span_s"] for c in rates)
    facts = {
        "window_s": window_s, "requests_sent": len(sent), "clients": rates,
        "counters_before": before, "counters_after": after,
        "compiles_in_window": window_compiles,
    }
    print(f"[generate] window {window_s:.3f}s: {len(sent)} sent, "
          f"{len(failed)} failed, {tokens_per_s:.3f} tokens/s over the "
          f"clients' spans {[round(c['span_s'], 1) for c in rates]} "
          f"({after['tokens_total'] - before['tokens_total']:.0f} tokens "
          f"inside the window by the program's counter), {window_compiles} "
          f"compiles inside it; errors "
          f"{[r.get('error') for r in failed][:3]} hung {hung} leaked {leaked} "
          f"stop {stop_err}", flush=True)

    memory = ctx.device.memory_stats()
    peak = ctx.device.memory_peak_bytes()
    del model, registry, srv, sched, v
    gc.collect()

    t0 = time.perf_counter()
    got = check_served(ctx, sample, program_logits)
    disagree = sum(int(np.sum(z.argmax(axis=1) != np.asarray(r["tokens"])))
                   for z, r in zip(program_logits, sample))
    print(f"[generate] reference {time.perf_counter() - t0:.1f}s over "
          f"{len(sample)} requests, {got['positions']} served tokens; fed "
          f"again, the executables put another token first at {disagree} of "
          f"them", flush=True)
    limits = check["limits"]
    checks = [("served_logit_gap", got["served"] if sample else float("nan"),
               limits["served_logit_gap"]),
              ("logit_rel_err", got["logit_err"], limits["logit_rel_err"]),
              ("requests_never_answered", float(len(hung)), 0.0)]
    end_to_end = {}
    if tokens_per_s:
        end_to_end["generate_tokens_per_s"] = tokens_per_s
    if latency:
        end_to_end["generate_latency_p95_ms"] = float(np.percentile(latency, 95))
    return {
        "attempted": len(sent) + len(hung), "failed": len(failed) + len(hung),
        "t_open": t_open, "end_to_end": end_to_end, "facts": facts,
        "trace_dir": trace_dir, "checks": checks,
        "memory_peak_bytes": peak, "memory_stats": memory,
    }
