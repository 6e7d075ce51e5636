"""refresh_excess_pct (%): how much longer a StatsBank refresh step takes
than a plain step: (median refresh-step time - median plain-step time) /
median plain-step time, from the traced window's step times (``step_s``:
the gap between the ends of two successive steps as the host saw them,
with steps dispatched ahead, so the chip's time for the step).  Nothing
without refresh steps and plain steps in the window."""

import statistics


def read(ctx):
    hist = ctx["history"]
    fresh = [h["step_s"] for h in hist if h.get("stats_refreshed", 0) >= 0.5]
    plain = [h["step_s"] for h in hist
             if "stats_refreshed" in h and h["stats_refreshed"] < 0.5]
    if not fresh or not plain:
        return None
    p = statistics.median(plain)
    return 100.0 * (statistics.median(fresh) - p) / p
