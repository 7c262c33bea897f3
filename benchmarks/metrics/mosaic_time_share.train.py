"""Layer `kernels`: device time inside Mosaic (Pallas) kernels, the ops
whose HLO line holds `tpu_custom_call`, as a share of the device's
busy time."""

from benchmarks import xplane


def read(run):
    capture = run["capture"]
    summary = xplane.device_summary(capture) if capture else None
    if summary is None:
        return None
    devices = sum(1 for d in capture["devices"] if d["ops"])
    return 100.0 * xplane.op_seconds(capture, xplane.is_mosaic) \
        / (summary["busy_s"] * devices)
