"""Model FLOP/s utilisation of training, in percent: the operations one
sample's forward and backward pass need (``flops.train_flops``, each
layer once, whatever layout runs it) times the samples per second of the
untraced window, over the chips' summed bf16 peak."""


def read(ctx):
    if not ctx["train"] or not ctx["steps"]:
        return None
    rate = ctx["steps"] * ctx["global_batch"] / ctx["window_s"]
    return 100.0 * ctx["train_flops"] * rate / (
        ctx["chips"] * ctx["peaks"]["flops_per_s"])
