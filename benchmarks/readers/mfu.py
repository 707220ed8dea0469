"""Model FLOP/s utilisation in percent: the benchmark's own FLOPs per
item x items per second / (chips x the device's published bf16 peak).
Only on a device the peak table knows."""


def read(window):
    f = window.scalars.get("flops_per_item")
    r = window.scalars.get("items_per_s")
    if window.peaks is None or f is None or r is None:
        return None
    return 100.0 * f * r / (window.scalars["chips"]
                            * window.peaks["flops_bf16"])
