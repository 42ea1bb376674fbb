"""The share of their roofline that the passes of compressed convolutional
attention's latent reach: the least time the chip could take for their
operations and bytes (the family's ``cca_mix_cost``, from shapes; the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s — bytes bound
it: one read of ``[q~ | k~]`` and one write of q" and k" forward, three
such moves backward) over ``cca_mix_ms``."""

from benchmark.metrics import _sparse, cca_mix_ms

UNIT = "%"
LAYER = "compressed attention"
MOVES = "step_ms"


def read(record, trace):
    return _sparse.roofline(record, cca_mix_ms.read(record, trace),
                            "cca_mix_cost")
