"""Device milliseconds a step in the flash-attention Pallas kernels,
device 0.  The trace does not carry a kernel's name, so the spans are
found by their custom-call target (``tpu_custom_call``: every Pallas
kernel of the step) and the metric is read only where the lowered step
holds none but the family's flash kernels; otherwise it is absent."""

UNIT = "ms"
LAYER = "kernels"
MOVES = "step_ms"


def read(record, trace):
    flash = getattr(record["family"], "FLASH_KERNELS", ())
    kernels = record["program"]["kernels"]
    if trace is None or not kernels or not all(
            any(f in k for f in flash) for k in kernels):
        return None
    d = trace["devices"][0]
    seconds = d["pallas_s"]["fwd"] + d["pallas_s"]["bwd"]
    return 1e3 * seconds / d["steps"] if seconds else None
