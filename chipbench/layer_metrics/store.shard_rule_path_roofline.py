"""The sharded rule path's share of its HBM roofline (%): what the FULLEST
shard's server side must move whatever implements it, over the chip's peak
HBM bandwidth, over ``store.shard_rule_path_device_ms`` (the chip where the
rule path takes longest, by that metric's reader).  The bytes: the shard's
own live keys at the ``1 + dim`` lanes of a pushed gradient that can be
other than zero (``w`` and ``V``; the lanes of ``z``, ``s``, ``c`` and ``S``
carry nothing), read once, and its own distinct rows at their whole ``4 + 2
dim`` lanes, read once and written once: from the program's counts of the
fullest shard, the gauges ``store_rule_keys_max_shard`` and
``store_rule_rows_max_shard``.  No id, sort, pad or dead lane is counted (a
chip walks all the batch's lanes today and owns a share of them), so this is
a lower bound and cannot pass 100.  A program without those gauges (the
parent; a store in one place) or a run without a trace reports nothing."""
import numpy as np

from chipbench import spec

# lanes of the row before V: w, z, s, c (families/difacto.STATE_LANES)
STATE_LANES = 4


def shard_rule_path_bytes(cfg: dict, keys: float, rows: float) -> float:
    """The least a shard that owns ``keys`` of a step's live keys and
    ``rows`` of its distinct rows moves on the server side of the step."""
    el = np.dtype(cfg["dtype"]).itemsize
    dim = int(cfg["dim"])
    return el * (keys * (1 + dim) + 2 * rows * (STATE_LANES + 2 * dim))


def read(ctx):
    ms = spec.metric_reader("store.shard_rule_path_device_ms").read(ctx)
    share = spec.metric_reader("store.rule_owner_max_share")
    keys = share.gauge("store_rule_keys_max_shard")
    rows = share.gauge("store_rule_rows_max_shard")
    if not ms or not ctx["peaks"] or not keys or not rows:
        return None
    least_s = (
        shard_rule_path_bytes(ctx["cfg"], keys, rows)
        / ctx["peaks"]["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / (ms / 1e3)
