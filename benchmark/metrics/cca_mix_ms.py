"""Device milliseconds a step in the passes of compressed convolutional
attention's latent, device 0, forward and backward: the self time of every
op under the scopes ``cca/conv`` (the depth-wise and the grouped causal
convolution over q and k), ``cca/qk_mean``, ``cca/norm_rope`` (the L2 norm,
its temperature and the partial rotation) and ``cca/shift`` (the values'
previous-token half) of a module named ``attn`` — what lies between the
projections (``cca/project``) and the flash kernels.  What
``cca_mix_roofline`` divides by.  Read only for a family that prices the
passes (``cca_mix_cost``); a program without the layer or its scopes, as
this metric's parent has, reads nothing."""

UNIT = "ms"
LAYER = "compressed attention"
MOVES = "step_ms"

PASSES = ("conv", "qk_mean", "norm_rope", "shift")


def in_passes(label: str) -> bool:
    """Whether an op label of ``tracered.label`` belongs to one of the
    latent's passes."""
    parts = label.split(" [")[0].split("/")
    return "attn" in parts and any(
        scope == "cca" and inner in PASSES
        for scope, inner in zip(parts, parts[1:]))


def read(record, trace):
    if trace is None or not hasattr(record["family"], "cca_mix_cost"):
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items()
                  if in_passes(label))
    return 1e3 * seconds / d["steps"] if seconds > 0 else None
