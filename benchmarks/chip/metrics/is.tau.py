"""is.tau: mean over the window's steps of the program's ``health.tau``
gauge (``obs/health.py``), the presample scheme's live τ."""


def read(ctx):
    if ctx.traffic["scheme"] != "presample" or not ctx.hook.taus:
        return None
    return sum(ctx.hook.taus) / len(ctx.hook.taus)
