"""1 - (seconds a device op ran, averaged over the chips) / (traced
window): the device's idle share, from the reduced trace."""


def read(evidence, params):
    trace = evidence.get("trace_reduced")
    if not trace or not trace.get("window_s"):
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]
