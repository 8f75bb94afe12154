"""Layer: whole step. Required operations of the window's steps (forward and
backward of every token, harness/flops.py, nothing recomputed) over the
window and over the chip's bf16 peak, in percent."""


def compute(env):
    f = env.facts
    if env.peak is None or not f.get("steps"):
        return None
    per_token = env.flops.lm_train_flops_per_token(env.config, f["seq_len"])
    rate = per_token * f["tokens_per_step"] * f["steps"] / f["window_s"]
    return 100.0 * rate / env.peak["bf16_flops_per_s"]
