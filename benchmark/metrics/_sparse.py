"""What the sparse-attention readers share: which op labels of
``tracered.label`` belong to the ``S`` layers' attention modules and to
their parts, and the sum of their self times.  The flax module is named
``attn``; under it the indexer's scopes are ``index/project``,
``index/scores``, ``index/topk``, ``index/select``, ``index/kl`` and the
selected-attention kernels' is ``flash_select``.  Read only for a family
that prices the selected attention (``sel_flash_cost``); a program without
the layer or its scopes, as these metrics' parent has, reads nothing."""


def _parts(label: str):
    return label.split(" [")[0].split("/")


def in_attention(label: str) -> bool:
    """Every op of an attention module: projections, norms, positions,
    indexer, selection, kernels, ``L_I``, with their transposes, and the
    casts of its parameters (which the compiler names after the
    parameter)."""
    stack = label.split(" [")[0]
    return "attn" in stack.split("/") or "['attn']" in stack


def in_indexer(label: str) -> bool:
    """The ops under the module's ``index`` scope."""
    parts = _parts(label)
    return "attn" in parts and "index" in parts[parts.index("attn"):]


def _kernel_under(label: str, scope: str) -> bool:
    parts = _parts(label)
    return parts[-1] == "pallas_call" and "attn" in parts and scope in parts


def is_selected_flash(label: str) -> bool:
    """The selected-attention Pallas kernels (forward, dq, dk/dv)."""
    return _kernel_under(label, "flash_select")


def is_scores_kernel(label: str) -> bool:
    return _kernel_under(label, "scores")


def is_kl_kernel(label: str) -> bool:
    return _kernel_under(label, "kl")


def milliseconds(record, trace, belongs):
    if trace is None or not hasattr(record["family"], "sel_flash_cost"):
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items() if belongs(label))
    return 1e3 * seconds / d["steps"] if seconds > 0 else None


def roofline(record, took_ms, cost_name: str):
    """The least time the chip could take for the family's ``cost_name``
    (the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s)
    over ``took_ms``, in percent."""
    if took_ms is None or record["peaks"] is None:
        return None
    cost = getattr(record["family"], cost_name)(
        record["cfg"], record["job"]["batch_per_chip"])
    least_s = max(cost["flops"] / record["peaks"]["bf16_flops_per_s"],
                  cost["bytes"] / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (took_ms * 1e-3)
