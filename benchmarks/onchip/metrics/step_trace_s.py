"""step_trace_s (s), beside ``setup_s``: host time the program spends
turning the train step into a program before its first compile: StatsBank
site discovery (the ``statsbank/init_bank`` span, which traces the loss
under ``eval_shape``) plus the first trace and the first lowering of the
jitted step (JAX's compile events for the function ``TrainLoop``
registers).  Read in this process from ``repro.obs.spans.RECORDER``; the
step's backend compile or persistent-cache read and the totals over every
function are logged beside it.  Nothing where the program has no recorder
or registered no traced step."""


def read(ctx):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    rec = spans.RECORDER
    steps = sorted(rec.step_names)
    first = {phase: [rec.first(n, phase) for n in steps]
             for phase in (spans.TRACE, spans.LOWER, spans.COMPILE)}
    if not steps or None in first[spans.TRACE] + first[spans.LOWER]:
        return None
    bank = rec.spans.get(spans.INIT_BANK)
    bank_s = 0.0 if bank is None else bank.total_ns * 1e-9
    trace_s, lower_s = sum(first[spans.TRACE]), sum(first[spans.LOWER])
    ctx["log"](f"step_trace_s: {spans.INIT_BANK} {bank_s:.6g} s, first "
               f"trace {trace_s:.6g} s and lowering {lower_s:.6g} s of "
               f"{steps}; its first compile or cache read "
               f"{first[spans.COMPILE]} s; all functions: trace "
               f"{rec.total(spans.TRACE):.6g} s, lowering "
               f"{rec.total(spans.LOWER):.6g} s, compile or cache read "
               f"{rec.total(spans.COMPILE):.6g} s, cache hits "
               f"{rec.counters.get(spans.CACHE_HITS, 0)}, misses "
               f"{rec.counters.get(spans.CACHE_MISSES, 0)}")
    return bank_s + trace_s + lower_s
