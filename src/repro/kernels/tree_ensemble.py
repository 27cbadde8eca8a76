"""Pallas TPU kernel: oblivious decision-tree inference (paper C4, MXU form).

The TPU-native adaptation of EmbML's if-then-else trees: branching becomes
three dense stages, all MXU/VPU work, no data-dependent control flow:

  1. ``xn = x @ sel``       — feature selection as a one-hot matmul
                              (sel[f, n] = 1 iff node n tests feature f),
                              pinned to exact f32 (``precision=HIGHEST``)
  2. ``cmp = xn <= thr``     — every node predicate in one vector compare
  3. ``score = cmp @ P``     — P = Ppos - Pneg holds +1/-1 for the branch
     each leaf's path takes at each node; a leaf is reached iff its score
     equals its count of "<=" branches (``target``), exactly one per sample.

Output is the reached leaf's class id per sample, picked by a masked max
over the leaves (no argmax, no gather: neither lowers to Mosaic).  Grid
over batch blocks; the tree tensors stay resident in VMEM — a depth-12 D6
tree has under a hundred leaves, and :func:`tree_vmem_bytes` bounds the
rest (see :func:`repro.kernels.ops.tree_predict`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.trees import ObliviousTree, TreeArrays, build_oblivious

from .mosaic import index_map

__all__ = ["tree_ensemble_pallas", "pack_tree", "tree_vmem_bytes"]


def pack_tree(tree: TreeArrays, pad_nodes: int = 128, pad_leaves: int = 128):
    """TreeArrays -> dense operands (sel, thr, path, target, classes).

    sel (F, N) f32; path (N, L) bf16 in {-1, 0, +1}; thr, target and
    classes are (1, N) / (1, L) f32 rows.  Padded to lane multiples:
    padding nodes always pass (threshold +inf) and sit on no path, and
    padding leaves get an unreachable target.
    """
    ob: ObliviousTree = build_oblivious(tree)
    n = max(pad_nodes, int(np.ceil(max(ob.path.shape[1], 1) / pad_nodes) * pad_nodes))
    l = max(pad_leaves, int(np.ceil(ob.path.shape[0] / pad_leaves) * pad_leaves))
    f = tree.n_features
    sel = np.zeros((f, n), np.float32)
    thr = np.full((1, n), np.float32(np.inf))
    for i, feat in enumerate(ob.node_feature):
        sel[feat, i] = 1.0
    thr[0, :len(ob.node_threshold)] = ob.node_threshold
    nn, ll = ob.path.shape[1], ob.path.shape[0]
    path = np.zeros((n, l), np.float32)
    path[:nn, :ll] = ob.path.T
    # score = cmp @ Ppos + (1 - cmp) @ Pneg == plen  <=>  cmp @ P == target
    target = np.full((1, l), -1.0, np.float32)  # unreachable for padding
    target[0, :ll] = ob.path_len - (ob.path == -1).sum(axis=1)
    classes = np.zeros((1, l), np.float32)
    classes[0, :ll] = ob.leaf_class
    return sel, thr, path.astype(jnp.bfloat16), target, classes


def tree_vmem_bytes(n_features: int, n_nodes: int, n_leaves: int,
                    block_batch: int) -> int:
    """Resident bytes of one grid step: the double-buffered tree operands
    plus the batch block and its (bb, N) / (bb, L) f32 intermediates."""
    operands = n_features * n_nodes * 4 + n_nodes * n_leaves * 2 \
        + (n_nodes + 2 * n_leaves) * 4
    rows = block_batch * (n_features + 2 * n_nodes + 3 * n_leaves + 1) * 4
    return 2 * operands + rows


def _kernel(x_ref, sel_ref, thr_ref, path_ref, target_ref, cls_ref, o_ref):
    xn = jax.lax.dot_general(x_ref[...], sel_ref[...],
                             (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)  # (bb, N)
    cmp = (xn <= thr_ref[...]).astype(jnp.bfloat16)
    # 0/1 against -1/0/+1 in bf16 with f32 accumulation: exact small ints.
    score = jax.lax.dot_general(cmp, path_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bb, L)
    hit = score == target_ref[...]
    cls = jnp.max(jnp.where(hit, cls_ref[...], -1.0), axis=1, keepdims=True)
    o_ref[...] = cls.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_batch", "interpret"))
def tree_ensemble_pallas(x: jax.Array, sel: jax.Array, thr: jax.Array,
                         path: jax.Array, target: jax.Array,
                         classes: jax.Array, block_batch: int = 256,
                         interpret: bool = False) -> jax.Array:
    """x: (B, F) float; packed tree operands from :func:`pack_tree`.
    Returns (B,) int32 class predictions.

    Ragged batches are handled here: B is padded up to the next multiple of
    ``block_batch`` (zero rows — rows are independent, so padding never
    perturbs real predictions) and the output is sliced back to B.  The
    output is a (B, 1) column so every block is a 2-D tile.
    """
    b0, f = x.shape
    n, l = path.shape
    rem = (-b0) % block_batch
    x = jnp.asarray(x, jnp.float32)
    if rem:
        x = jnp.pad(x, ((0, rem), (0, 0)))
    b = b0 + rem
    rows = index_map(lambda i: (i, 0))
    whole = index_map(lambda i: (0, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(b // block_batch,),
        in_specs=[
            pl.BlockSpec((block_batch, f), rows),
            pl.BlockSpec((f, n), whole),
            pl.BlockSpec((1, n), whole),
            pl.BlockSpec((n, l), whole),
            pl.BlockSpec((1, l), whole),
            pl.BlockSpec((1, l), whole),
        ],
        out_specs=pl.BlockSpec((block_batch, 1), rows),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.int32),
        interpret=interpret,
    )(x, sel, thr, path, target, classes)
    return out[:b0, 0]
