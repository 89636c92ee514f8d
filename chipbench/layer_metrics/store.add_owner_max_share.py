"""The fullest shard's share of the live lanes a step's add push kept (%):
how far the partition of a sharded add store is from even, where the push
runs on the shards that own the rows (``core/store._push_add_on_shards``).
With ``ps`` = 4 servers 25 is even; cell 16's contiguous blocks give shard 1
10.12 of an example's 26 ids, 38.9 % (the configuration's
``assumed.partitioning``), and a step waits for that shard's tile kernel.
From the program's own counters, the gauges ``store_push_lanes_max_shard``
and ``store_push_kernel_lanes`` that ``StreamingDriver`` sets from the last
dispatch's outputs once the loop has ended (no fetch inside the window).  A
program without them (the parent; a store in one place; a push XLA's
scatter-add takes) reports nothing."""

from chipbench import spec


def read(ctx):
    # the program's gauges as cell 12's reader of the rule's shares finds them
    gauge = spec.metric_reader("store.rule_owner_max_share").gauge
    most = gauge("store_push_lanes_max_shard")
    lanes = gauge("store_push_kernel_lanes")
    return 100.0 * most / lanes if most and lanes else None
