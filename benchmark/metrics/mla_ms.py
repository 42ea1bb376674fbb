"""Device milliseconds a step in latent attention OUTSIDE its kernels,
device 0, forward and backward: the self time of every op under the
``mla/…`` trace scopes of a flax module named ``attn`` other than
``mla/attend`` — the down-projections (``mla/q_down``, ``mla/kv_down``),
the latents' norms (``mla/norm``), the up-projections (``mla/q_up``,
``mla/kv_up``) with their recomputation in the backward pass, the rotation
and the lanes' padding (``mla/rope``), the output projection
(``mla/out``) — and of the casts of the module's parameters, which the
compiler names after the parameter (``params['layer_0']['attn']['q_b']
['kernel']``).  Read only for a family that prices the layer
(``mla_cost``); a program without the layer or its scopes, as this
metric's parent has, reads nothing."""

UNIT = "ms"
LAYER = "latent attention"
MOVES = "step_ms"

PARAMETERS = ("q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "proj")


def latent_part(label: str):
    """The part of latent attention an op label of ``tracered.label``
    belongs to — the scope under ``mla`` (``q_down`` … ``attend``,
    ``out``), ``"cast"`` for a cast named after one of the module's
    parameters — or None for an op of another layer."""
    stack = label.split(" [")[0]
    parts = stack.split("/")
    if "attn" in parts:
        rest = parts[parts.index("attn"):]
        if "mla" in rest[:-1]:
            return rest[rest.index("mla") + 1]
    if any(f"['attn']['{p}']" in stack for p in PARAMETERS):
        return "cast"
    return None


def read(record, trace):
    if trace is None or not hasattr(record["family"], "mla_cost"):
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items()
                  if latent_part(label) not in (None, "attend"))
    return 1e3 * seconds / d["steps"] if seconds > 0 else None
