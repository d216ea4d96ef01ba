"""Share of their roofline that the convolutions of training reach, in
percent: the least time the chips could take for the model's conv
operations and bytes (``flops.conv_flops``/``conv_bytes``, each layer
once), the larger of operations over peak and bytes over bandwidth, over
the device time of every operation whose HLO holds a convolution, summed
over the chips. Which bound applies is logged."""


def read(ctx):
    tr = ctx.get("trace")
    if not ctx["train"] or tr is None or not tr["conv_s"]:
        return None
    samples = ctx["trace_steps"] * ctx["global_batch"]
    t_flops = ctx["conv_flops"] * samples / ctx["peaks"]["flops_per_s"]
    t_bytes = ctx["conv_bytes"] * samples / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["log"](f"conv_roofline.train: bound by "
               f"{'operations' if t_flops >= t_bytes else 'bytes'} "
               f"({t_flops:.6f} s vs {t_bytes:.6f} s) over "
               f"{tr['conv_s']:.6f} s of conv ops")
    return 100.0 * max(t_flops, t_bytes) / tr["conv_s"]
