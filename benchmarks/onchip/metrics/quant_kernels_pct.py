"""quant_kernels_pct (%), beside ``train_tokens_per_s``: the share of the
traced window's device busy time spent in the S2FP8 quantization kernels
(Eq. 3-4 statistics, the forward map to the payload, its inverse, the
Eq. 5 round trip and its fused-statistics form), told by the names the
program gives them (``repro.obs.spans.QUANT_KERNELS``, the HLO instruction
names of their Mosaic calls): their device self time, averaged over the
cell's chips, over ``busy_s``.  0 where the cell's policy is not S2FP8 and
no such kernel ran; nothing where an S2FP8 cell's trace holds none of them,
or where the program names no kernels (no ``repro.obs.spans``)."""

import trace_reduce


def read(ctx):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    red = ctx["trace"]
    found = red.kernel_calls(
        lambda op: "tpu_custom_call" in op
        and trace_reduce.base_name(op) in spans.QUANT_KERNELS)
    if not found:
        return None if ctx["job"]["policy"].startswith("s2fp8") else 0.0
    by_kernel = {}
    for op, t, _ in found:
        name = trace_reduce.base_name(op)
        by_kernel[name] = by_kernel.get(name, 0.0) + t
    secs = sum(by_kernel.values()) / red.n_devices
    each = ", ".join(f"{k} {v:.6g} s" for k, v in sorted(by_kernel.items()))
    ctx["log"](f"quant_kernels_pct: {secs:.6g} s of {red.busy_s:.6g} s busy "
               f"({each}) in {ctx['steps']} steps")
    return 100.0 * secs / red.busy_s
