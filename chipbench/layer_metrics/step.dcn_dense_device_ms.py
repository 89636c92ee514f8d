"""Device time a step of the worker's dense net (ms) where it is DLRM-DCNv2's
(``models/dlrm_dcnv2.py``): the ops under ``ps.dense_bottom`` +
``ps.dense_interact`` + ``ps.dense_top`` inside ``ps.compute``, forward and
backward pass (the bottom MLP, the cross network, the over arch with the
loss's gradient); ``step.dense_device_ms``'s reading, by that metric's own
reader (it lists cell 10, and a list is not to be edited).  Adagrad on the
leaves (``ps.dense_adagrad``) and the bags' two ends (``ps.bag_pool``,
``ps.bag_grad_spread``) are left out.  A program without those scopes
reports nothing."""
from chipbench import spec


def read(ctx):
    return spec.metric_reader("step.dense_device_ms").read(ctx)
