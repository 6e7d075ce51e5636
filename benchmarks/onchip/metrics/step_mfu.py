"""step_mfu.train (%): the whole training step's share of the chips'
bf16 peak, beside ``train_tokens_per_s``: model operations per token
(``counts.model_flops_per_token``, no recompute) times the tokens of the
steps in the traced window, over the window's length on the profiler's
clock, chips and peak."""


def read(ctx):
    c, job = ctx["counts"], ctx["job"]
    flops = (c.model_flops_per_token(ctx["sizes"], job) * ctx["steps"]
             * job["batch"] * job["seq"])
    return (100.0 * flops / ctx["trace"].window_s
            / (ctx["chips"] * ctx["peaks"]["bf16_flops"]))
