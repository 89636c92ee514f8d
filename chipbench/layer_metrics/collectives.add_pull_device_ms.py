"""Device time a step inside collectives (ms) in a cell whose ADD push runs
on the shards that own the rows: ``collectives.device_ms``'s reading, by
that metric's own reader (it lists cell 4, and a list is not to be edited):
the union of the collective ops of the busiest chip, each from its start to
its end there, so it holds the wait for the slowest chip.  Such a step
should hold ONE collective of any size, the pull's all-reduce of the
gathered rows (GSPMD's, behind ``jnp.take`` of the row-sharded table:
``f32[32768,26,128]``, 436 MB, in cell 16), and the gather of two counts
that ends the push (``core/store._push_add_on_shards``), which moves 32
bytes and lasts as long as the slowest shard's tile kernel is behind this
chip's: a second collective of rows appearing here is a finding."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("collectives.device_ms").read(ctx)
