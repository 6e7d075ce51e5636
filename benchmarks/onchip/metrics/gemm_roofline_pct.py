"""gemm_roofline_pct (%): the payload GEMM kernels' share of their
roofline.  The least time of every payload GEMM call in the traced window
(``counts.gemm_call``: the call's published M, K and N read from its HLO
text, 1-byte operands, f32 output), over the device time of those calls.
The trace names a Pallas call after the jitted function around it:
``s2fp8_matmul_pallas`` (``kernels/s2fp8_matmul.py``, kernel
``_matmul_kernel``) and ``s2fp8_matmul_batched_pallas``
(``_batched_matmul_kernel``).  Nothing when the trace holds no such call."""

import trace_reduce

KERNELS = ("s2fp8_matmul_pallas", "s2fp8_matmul_batched_pallas")


def is_gemm(name):
    return ("tpu_custom_call" in name
            and trace_reduce.base_name(name) in KERNELS)


def read(ctx):
    c, s, job = ctx["counts"], ctx["sizes"], ctx["job"]
    found = ctx["trace"].kernel_calls(is_gemm)
    if not found:
        return None
    calls = [c.gemm_call(op, s, job, n) for op, _, n in found]
    secs = sum(t for _, t, _ in found)
    ideal = c.ideal_seconds(calls, ctx["peaks"])
    ctx["log"](f"gemm_roofline_pct: {sum(x.count for x in calls)} calls in "
               f"{ctx['steps']} steps; least time {ideal['seconds']:.6g} s, "
               f"{ideal['compute_bound_s']:.6g} s of it compute-bound, "
               f"{ideal['memory_bound_s']:.6g} s memory-bound; kernel time "
               f"{secs:.6g} s")
    return 100.0 * ideal["seconds"] / secs
