"""DeviceKV: the device-resident half of the paged KV pool, mesh-aware.

Ownership contract (the other half lives in ``kv_pool.PagedKVPool``):

  * **Replicated on host** — page tables, the refcounted prefix trie, free
    lists, cursors.  The host pool plans in *logical* pages; it never sees
    a shard.  Preemption, COW planning, prefix matching and admission are
    therefore global decisions, identical at every ``tp``.
  * **Sharded on device** — the page buffers ``k_pages``/``v_pages``
    ((L, P, page, KV, hd)) and the int8 per-(page, kv_head) scale rows
    ``k_scales``/``v_scales`` ((L, P, KV)) are partitioned on their KV-head
    axis over the mesh's ``"model"`` axis: each shard owns the pages of its
    own KV heads, the software twin of the paper's per-array weight/KV
    residency.  A KV-head count the model axis does not divide leaves the
    pool replicated (``kv_shard == 1``) — GQA-correct, never uneven.
  * **Who may write a page** — only the mixed step's span writes (masked by
    ``write_start``/span sink-redirects) and ``cow_copy``.  Both operate on
    the *page* axis (axis 1), which is never sharded, so every shard
    performs the same page-granular scatter on its local KV-head slice —
    no cross-shard traffic for writes or COW forks.
  * **Snapshot** — ``export()`` gathers every shard into host arrays (a
    snapshot is mesh-shape independent); ``load()`` re-shards a host tree
    onto whatever mesh the restoring engine runs, so a ``tp=8`` snapshot
    restores onto ``tp=1`` and vice versa.  ``check_shards()`` is the
    per-shard recovery invariant: every leaf must sit on the mesh with
    exactly the placement this contract prescribes.

With ``mesh=None`` the class is a thin owner of the plain single-device
pool pytree — no device_put, no constraints — so the ``tp=1`` engine path
is bit-identical to the pre-mesh code.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer as T
from repro.models.config import ModelConfig


def kv_shard_size(cfg: ModelConfig, mesh: Optional[Mesh]) -> int:
    """How many ways the pool's KV-head axis is actually split: the mesh's
    "model" axis size when it divides ``n_kv_heads``, else 1 (replicated —
    the same divisibility guard ``sharding/api.logical`` applies)."""
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    tp = dict(mesh.shape).get("model", 1)
    return tp if tp > 1 and cfg.n_kv_heads % tp == 0 else 1


def pool_shardings(pool, mesh: Mesh, kv_shard: int):
    """NamedSharding pytree for a paged pool: page buffers (L, P, page, KV,
    hd) split on KV (axis 3), scale rows (L, P, KV) split on KV (axis 2);
    everything replicated when ``kv_shard == 1``."""

    def one(leaf):
        if kv_shard <= 1:
            return NamedSharding(mesh, P())
        if leaf.ndim == 5:    # k_pages / v_pages
            return NamedSharding(mesh, P(None, None, None, "model", None))
        if leaf.ndim == 3:    # k_scales / v_scales
            return NamedSharding(mesh, P(None, None, "model"))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(one, pool)


class DeviceKV:
    """Owner of the device-side paged pool (pages + quant scales).

    The engine reads/writes ``self.pool`` through a property, so the jitted
    mixed step and the COW copy keep donating and replacing the pytree
    exactly as before — DeviceKV adds placement (mesh sharding), transfer
    (export/load for snapshots) and the per-shard invariant check.
    """

    def __init__(self, cfg: ModelConfig, n_pages: int, page_size: int,
                 kv_dtype: Optional[str] = None,
                 mesh: Optional[Mesh] = None):
        self.cfg = cfg
        self.n_pages = n_pages
        self.page_size = page_size
        self.mesh = mesh
        self.kv_shard = kv_shard_size(cfg, mesh)
        def init():
            return T.init_paged_pool(cfg, n_pages, page_size,
                                     kv_dtype=kv_dtype)

        if mesh is not None:
            # made on its shards: the logical pool of a tp engine is sized
            # per shard and need not fit one device whole
            self.shardings = pool_shardings(jax.eval_shape(init), mesh,
                                            self.kv_shard)
            self.pool = jax.jit(init, out_shardings=self.shardings)()
        else:
            self.shardings = None
            self.pool = init()

    # -- snapshot transfer -------------------------------------------------

    def export(self) -> dict:
        """Gather every shard to host numpy — the snapshot form.  On a
        sharded pool ``device_get`` performs the cross-shard gather, so the
        exported tree is mesh-shape independent."""
        return jax.device_get(self.pool)

    def load(self, host_pool) -> None:
        """Re-shard a host (or single-device) pool tree onto this DeviceKV's
        placement — the restore half of the snapshot contract."""
        if self.shardings is not None:
            self.pool = jax.device_put(host_pool, self.shardings)
        else:
            self.pool = jax.tree_util.tree_map(jnp.asarray, host_pool)

    # -- invariants --------------------------------------------------------

    def check_shards(self) -> None:
        """Per-shard recovery invariant: every pool leaf lives on the mesh
        with the contract's placement, and each shard's KV-head slice has
        the expected per-shard shape.  No-op without a mesh."""
        if self.mesh is None:
            return
        expected = self.shardings
        flat, _ = jax.tree_util.tree_flatten(self.pool)
        specs, _ = jax.tree_util.tree_flatten(expected)
        for leaf, want in zip(flat, specs):
            got = leaf.sharding
            # specs compare by equivalence: jit outputs trim trailing Nones
            assert isinstance(got, NamedSharding) \
                and got.is_equivalent_to(want, leaf.ndim), \
                f"pool leaf sharding drifted: {got} != {want}"
            kv_axis = leaf.ndim - 2 if leaf.ndim == 5 else leaf.ndim - 1
            per_shard = leaf.shape[kv_axis] // self.kv_shard
            for shard in leaf.addressable_shards:
                assert shard.data.shape[kv_axis] == per_shard, \
                    (shard.data.shape, kv_axis, per_shard)


__all__ = ["DeviceKV", "kv_shard_size", "pool_shardings"]
