"""Host seconds from ``import horovod_tpu`` done to ``hvd.init()`` and
``hvd.ranks_mesh()`` returned: native core (built only if absent),
topology, mesh, controller."""

UNIT = "s"
LAYER = "entry, topology, mesh"
MOVES = "setup_s"


def read(record, trace):
    return record["phases"].get("init_s")
