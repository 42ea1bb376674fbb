"""Device milliseconds a step in the multi-token-prediction module, device
0, forward and backward: the self time of every op under a flax module
named ``mtp`` (the two norms and ``eh_proj``, the module's own attention
and expert layer with their kernels, its final norm), of the casts of its
parameters (``params['mtp']...``) and of the module's cross-entropy pass
over the shared head, which ``ops.losses.multi_token_xent`` runs under the
trace scope ``mtp`` (``jvp(mtp)/xent/loss``, ``transpose(jvp(mtp))/
xent/grad``).  The module's expert layer counts in ``moe_ms`` too, its
attention kernels in ``gqa_flash_ms``.  Read only for a family with a
prediction module (it names ``mtp_pattern``); a program without the module
or the scope, as this metric's parent has, reads nothing."""

import re

UNIT = "ms"
LAYER = "multi-token prediction"
MOVES = "step_ms"

# The module's name as a part of a name stack: bare, or inside the
# wrappers autodiff puts around the outermost scope.
_PART = re.compile(r"(?:\w+\()*mtp\)*")


def in_prediction_module(label: str) -> bool:
    """Whether an op label of ``tracered.label`` belongs to the
    prediction module or to its cross-entropy pass."""
    stack = label.split(" [")[0]
    return "['mtp']" in stack or any(
        _PART.fullmatch(part) for part in stack.split("/"))


def read(record, trace):
    if trace is None or not hasattr(record["family"], "mtp_pattern"):
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items()
                  if in_prediction_module(label))
    return 1e3 * seconds / d["steps"] if seconds > 0 else None
