"""Device milliseconds a step in the state-space mixers' two elementwise
passes, device 0, forward and backward: the self time of the ops under the
scopes ``conv`` (the depthwise causal convolution and its activation, with
the forward replayed inside the mixer's ``jax.checkpoint``) and
``gate_norm`` (the gate and the grouped RMSNorm) of a module named ``ssm``
— ``ssm_conv_fwd`` / ``ssm_conv_bwd`` and ``ssm_gate_fwd`` /
``ssm_gate_bwd`` where ``ops/mixer_passes.py`` takes its kernels, XLA's
fusions where it does not.  What ``mixer_pass_roofline`` divides by.  Read,
as ``ssm_ms``, only for a family that prices the scan (``ssd_cost``); a
program without the layer or its scopes reads nothing."""

from benchmark.metrics import ssm_ms

UNIT = "ms"
LAYER = "state-space mixers"
MOVES = "step_ms"

PASSES = ("conv", "gate_norm")


def in_passes(label: str) -> bool:
    """Whether an op label of ``tracered.label`` belongs to the mixer's
    ``conv`` or ``gate_norm`` scope."""
    parts = label.split(" [")[0].split("/")
    return "ssm" in parts and any(
        scope in parts[parts.index("ssm"):] for scope in PASSES)


def read(record, trace):
    return ssm_ms.milliseconds(record, trace, in_passes)
