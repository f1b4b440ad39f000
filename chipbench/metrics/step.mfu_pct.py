"""The whole step's share of the chip's bf16 peak (%): operations the updates of the window
needed (chipbench/flops.py, from the configuration's shapes) over window x peak x chips."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    window = ctx["window"]
    if window.updates == 0:
        return None
    per_update = ctx["program"].flops_per_update(ctx["cfg"], ctx["param_shapes"])
    return 100.0 * per_update * window.updates / (window.elapsed * ctx["peak"]["bf16_flops_per_s"] * ctx["chips"])
