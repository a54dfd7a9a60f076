"""Layer: device. 1 - (union of the device's operation intervals / traced
window), in percent. The traced window starts with the first working engine
iteration, so it includes the ramp from an empty batch."""


def reduce(scrapes, trace, run):
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
