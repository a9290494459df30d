"""The CNN gradient task's two transfer paths.

Served params that are host arrays (a remote client's, decoded from the
wire) go to the device in one packed buffer and their gradients come
back in one; params already on the device keep the per-leaf call.  Both
must return what :func:`repro.models.cnn.loss_and_grads` computes on the
same params and rows, bit for bit, in the same tree, dtypes and shapes.
"""
from unittest import mock

import jax
import numpy as np
import pytest

from repro.configs.paper_cnn import FABRIC_CNN, FIG2_CNN, FIG4_CNN
from repro.models import cnn
from repro.obs import Tracer, trace
from repro.sharding.spec import values_tree


@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
@pytest.mark.parametrize("leaves", ["host", "device"])
@pytest.mark.parametrize("ccfg", [FIG2_CNN, FIG4_CNN, FABRIC_CNN],
                         ids=["fig2", "fig4", "tiny"])
def test_grad_task_equals_loss_and_grads_bit_for_bit(ccfg, leaves, traced):
    rows = ccfg.batch_size
    params = jax.device_get(values_tree(cnn.init_cnn(jax.random.PRNGKey(2),
                                                     ccfg)))
    served = params if leaves == "host" else jax.device_put(params)
    task = cnn.CnnGradShard(ccfg, n_rows=3 * rows, seed=4)
    tr = Tracer() if traced else None
    with mock.patch.object(cnn, "_pack", wraps=cnn._pack) as pack, \
            trace.use(tr):
        out = task((rows, 2 * rows),
                   {"weights": {"round": 7, "params": served}})
    # host leaves take one packed buffer; device leaves no host trip
    assert pack.call_count == (leaves == "host")
    if traced:
        n_leaves = len(jax.tree_util.tree_leaves(params))
        moved = {e["name"]: e["args"]["arrays"] for e in tr.events()
                 if e["name"] in ("grad.h2d", "grad.d2h")}
        # device leaves: the rows go in, each gradient leaf comes back
        assert moved == ({"grad.h2d": 1, "grad.d2h": 1} if leaves == "host"
                         else {"grad.h2d": 2, "grad.d2h": n_leaves})

    images, labels = cnn.shard_dataset(ccfg, 3 * rows, 4)
    loss, grads = jax.device_get(cnn.loss_and_grads(ccfg)(
        params, images[rows:2 * rows], labels[rows:2 * rows]))
    assert out["round"] == 7
    assert isinstance(out["loss"], float) and out["loss"] == float(loss)
    assert (jax.tree_util.tree_structure(out["grad"])
            == jax.tree_util.tree_structure(grads))
    for got, want in zip(jax.tree_util.tree_leaves(out["grad"]),
                         jax.tree_util.tree_leaves(grads)):
        assert isinstance(got, np.ndarray)
        assert (got.dtype, got.shape) == (want.dtype, want.shape)
        np.testing.assert_array_equal(got, want)
