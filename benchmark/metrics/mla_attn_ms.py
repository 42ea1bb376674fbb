"""Device milliseconds a step in latent attention's kernels, device 0,
forward and backward: the self time of the ``pallas_call`` ops under the
trace scope ``mla/attend`` of a flax module named ``attn`` (the flash
family's forward and backward at keys of 192 against values of 128, and
nothing else: the lanes are padded under ``mla/rope``).  Read only for a
family that prices the layer (``mla_cost``); a program without the layer
or the scope, as this metric's parent has, reads nothing."""

from benchmark.metrics.gqa_flash_ms import is_attention_kernel
from benchmark.metrics.mla_ms import latent_part

UNIT = "ms"
LAYER = "latent attention"
MOVES = "step_ms"


def read(record, trace):
    if trace is None or not hasattr(record["family"], "mla_cost"):
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items()
                  if is_attention_kernel(label)
                  and latent_part(label) == "attend")
    return 1e3 * seconds / d["steps"] if seconds > 0 else None
