"""device_idle_pct: share of the traced window in which no operation ran
on the device (1 - union of the "XLA Ops" intervals / window), averaged
over the chips used."""


def read(ctx):
    d = ctx.device
    if not d.get("window_s"):
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
