"""qflash_roofline_pct (%): the payload flash-attention kernels' share of
their roofline.  The least time of every qflash forward, dq and dk/dv
call in the traced window (``counts.qflash_call``: causal half, published
head size, 1-byte Q/K/V/dO), over the device time of those calls.  The
trace names a Pallas call after the function around it (``checkpoint``,
``branch_0_fun``, ...), so the kernels of ``kernels/flash_attention.py``
are told by their operands: a TPU custom call that reads three or more
1-byte [heads, S, d] payloads of one S and d (Q and dO at B*H heads, K and
V at B*KV under grouped-query attention).  Nothing when the trace holds
none."""

import counts

ONE_BYTE = ("f8e5m2", "f8e4m3fn")


def is_qflash(name):
    if "tpu_custom_call" not in name or "custom-call(" not in name:
        return False
    _, ins = counts._op_shapes(name)
    payloads = [dims[1:] for t, dims in ins if t in ONE_BYTE and len(dims) == 3]
    return len(payloads) >= 3 and len(set(payloads)) == 1


def read(ctx):
    c, s, job = ctx["counts"], ctx["sizes"], ctx["job"]
    found = ctx["trace"].kernel_calls(is_qflash)
    if not found:
        return None
    calls = [c.qflash_call(op, s, job, n) for op, _, n in found]
    secs = sum(t for _, t, _ in found)
    ideal = c.ideal_seconds(calls, ctx["peaks"])
    kinds = {}
    for x in calls:
        kinds[x.name] = kinds.get(x.name, 0) + x.count
    ctx["log"](f"qflash_roofline_pct: calls {kinds} in {ctx['steps']} "
               f"steps; least time {ideal['seconds']:.6g} s, "
               f"{ideal['compute_bound_s']:.6g} s of it compute-bound; "
               f"kernel time {secs:.6g} s")
    return 100.0 * ideal["seconds"] / secs
