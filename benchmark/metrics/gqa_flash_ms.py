"""Device milliseconds a step in the flash-attention kernels, device 0,
forward and backward, found by their label: the self time of the
``pallas_call`` ops under a flax module named ``attn``.  ``flash_ms``
takes every ``tpu_custom_call`` span of the step, and the TPU compiler
makes the expert layers' grouped matmuls such calls too, so it is read
only where the step holds no other; this one is read where it is not: a
family that prices the kernels (``flash_cost``) and names no
``FLASH_KERNELS``.  A program without the layer reads nothing."""

UNIT = "ms"
LAYER = "kernels"
MOVES = "step_ms"


def is_attention_kernel(label: str) -> bool:
    """Whether an op label of ``tracered.label`` is a Pallas kernel of an
    attention layer."""
    parts = label.split(" [")[0].split("/")
    return parts[-1] == "pallas_call" and "attn" in parts


def read(record, trace):
    family = record["family"]
    if (trace is None or not hasattr(family, "flash_cost")
            or hasattr(family, "FLASH_KERNELS")):
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items()
                  if is_attention_kernel(label))
    return 1e3 * seconds / d["steps"] if seconds > 0 else None
