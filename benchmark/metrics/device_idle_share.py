"""Layer device: 1 - (union of the device's operation intervals) / (traced
window), from the profiler's trace, averaged over the chips."""


def read(obs):
    t = obs.trace
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
