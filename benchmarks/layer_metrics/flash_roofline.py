"""Layer: kernels. The least time the chip could take for the flash kernels'
calls in the traced window (for each of flash_fwd, flash_bwd_dq, flash_bwd_dkv
the larger of operations / bf16 peak and bytes / HBM bandwidth, from
harness/flops.py: two required T x T x Dh products a kernel, halved for the
causal mask; inputs and outputs once) over their device time in the trace, in
percent. At B 8, T 1024, Dh 64 in bfloat16 the forward is bound by its
operations (65 us a call against 62 us for its bytes), the two backward
kernels by their bytes (78 and 93 us against 65 us)."""


def compute(env):
    if env.trace is None or env.peak is None:
        return None
    fl, xp = env.flops, env.xplane
    stats = xp.kernel_stats(env.trace, fl.FLASH_KERNELS)
    if not stats:
        return None
    m = fl.lm_dims(env.config)
    bh = env.facts["batch"] * m["heads"]
    least = 0.0
    for k, (_, calls) in stats.items():
        t, _ = fl.roofline_seconds(
            fl.flash_kernel_flops(k, bh, env.facts["seq_len"], m["d_head"]),
            fl.flash_kernel_bytes(k, bh, env.facts["seq_len"], m["d_head"]),
            env.peak)
        least += calls * t
    return 100.0 * least / sum(sec for sec, _ in stats.values())
