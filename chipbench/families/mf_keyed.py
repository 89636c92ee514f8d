"""Online matrix factorisation with the user factors partitioned over keyed
workers: ``families/mf.py``'s logic, store, rows and bytes, under a mesh
whose ``dp`` axis is the workers.  What differs is the stream: the flat
stream ``datagen.rating_batches`` draws goes through the PROGRAM's keyed
shuffle (``data/keyed.KeyedRouter``, the router ``StreamingDriver`` puts in
front of the step), so that lane block ``w`` of a staged microbatch holds
only users of worker ``w``."""
from __future__ import annotations

from typing import Dict, List

from chipbench import datagen
from chipbench.families import mf
from chipbench.families.mf import (  # noqa: F401
    STEP_PROGRAM,
    hbm_bytes_per_step,
    rows,
    topk_check,
)

# flat batches drawn beyond the pool's count, so that every worker's block of
# every pooled microbatch is full (users are uniform: after n + SPARE flat
# batches the emptiest worker is short of n blocks only beyond 30 sigma)
SPARE = 1


def build(cfg: dict, seed: int, mesh):
    """``families/mf.build`` under the configuration's mesh.  A caller that
    brings none (the tests that walk every configuration on the CPU's
    virtual devices) gets the configuration's own, so that what they hold
    to the reference is the keyed step and not one worker's."""
    if mesh is None:
        import jax

        from flink_parameter_server_tpu.parallel.mesh import make_mesh

        dp, ps = int(cfg["mesh"]["dp"]), int(cfg["mesh"]["ps"])
        mesh = make_mesh(dp, ps, devices=jax.devices()[: dp * ps])
    return mf.build(cfg, seed, mesh)


def host_batches(cfg: dict, traffic: dict, seed: int, n: int) -> List[Dict]:
    from flink_parameter_server_tpu.data.keyed import KeyedRouter
    from flink_parameter_server_tpu.models.matrix_factorization import (
        worker_block_rows,
    )

    workers = int(cfg["mesh"]["dp"])
    flat = datagen.rating_batches(
        cfg["num_users"], cfg["num_items"], cfg["batch"], n + SPARE,
        item_keys=traffic["keys"], seed=seed,
    )
    router = KeyedRouter(
        workers, worker_block_rows(cfg["num_users"], workers), key="user",
        block=int(cfg["batch_per_worker"]),
    )
    keyed = []
    for batch in router.route(flat):
        keyed.append(batch)
        if len(keyed) == n:
            break
    return keyed
