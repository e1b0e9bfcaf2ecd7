"""Two-level rerank memory hierarchy: host-resident full-precision tier.

The (n, D) float32 rerank store is D/d * 4 bytes per vector larger than
the int8 codes the fine-scan kernels stream -- it dominates device memory
long before the working set does. The DiskANN/SPANN-style layout keeps the
hot reduced codes near compute and demotes the full-precision tier one
level out, moving only the per-query candidate rows (kappa << n) across
the boundary. This module maps that hierarchy onto the SearchArtifacts
contract:

* :class:`HostStore` -- an (n, D) store that lives in HOST memory (numpy)
  but rides the ``ServingState`` pytree as STATIC aux data with zero
  array leaves, so the compiled search step never materializes it in
  device memory, ``jit`` never traces it, and swap/treedef checks compare
  it by (shape, dtype) -- a refreshed store with new contents is
  treedef-equal and swaps in with zero recompiles, exactly like a device
  leaf with unchanged aval.
* :class:`ShardedHostStore` -- the spill-to-host counterpart of
  ``ShardedIndex``: equal contiguous row shards held as separate host
  buffers (one per shard's spilled rerank tier), same API, global-id
  routing in ``take``.

Both keep ``x_full``'s consumer surface: ``np.asarray`` / ``jnp.asarray``
(``__array__``), fancy row indexing, and the functional
``.at[ids].set(rows)`` update ``streaming.insert_rows`` issues -- so the
streaming bridge and the benches are tier-agnostic. The one operation a
host tier CANNOT serve is a traced gather (``rerank`` inside ``jit``);
the serving engine runs the two-stage pipeline instead (device candidates
-> host ``take`` of kappa rows -> async ``device_put`` -> compiled
rerank), see :mod:`repro.serve.engine`.

Where the runtime's memories API can express device-addressable host
memory (``memory_kind="pinned_host"``: TPU, some GPUs), ``demote`` is
still the right call -- the engine's prefetch ``device_put`` then sources
from pinned pages; :func:`supports_pinned_host` probes the capability
(False on CPU backends, whose only memory space IS host memory).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["HostStore", "ShardedHostStore", "demote", "promote",
           "host_store", "host_arrays", "from_host_arrays",
           "supports_pinned_host"]


class _At:
    """``store.at[ids].set(rows)``: the jax functional-update surface,
    copy-on-write against host memory (only the touched shard buffers are
    copied for sharded stores)."""

    def __init__(self, store):
        self._store = store

    def __getitem__(self, ids):
        store = self._store

        class _Ref:
            @staticmethod
            def set(rows):
                return store.set_rows(ids, rows)

        return _Ref()


class _HostTier:
    """Shared surface of the host-resident stores (see module docstring)."""

    @property
    def ndim(self) -> int:
        return 2

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def at(self) -> _At:
        return _At(self)

    def __len__(self) -> int:
        return self.shape[0]

    def __array__(self, dtype=None, copy=None):
        a = self._materialize()
        return a.astype(dtype) if dtype is not None else a

    def __getitem__(self, idx):
        return self._materialize()[idx] \
            if isinstance(idx, tuple) else self.gather_rows(idx)

    # Treedef/aval identity: the serving contracts (jit cache keys,
    # ``ServingEngine._check_swap_compatible``) compare states by treedef,
    # and a host store IS treedef (aux) data -- equality by (type, shape,
    # dtype) makes a refreshed store with new CONTENTS swap-compatible
    # (zero recompiles), while a reshaped/retyped one is refused, exactly
    # matching the aval rule device leaves live under.
    def _aval(self):
        return (type(self).__name__, tuple(self.shape), str(self.dtype))

    def __eq__(self, other):
        return isinstance(other, _HostTier) and self._aval() == other._aval()

    def __hash__(self):
        return hash(self._aval())

    def __repr__(self):
        n, d = self.shape
        return (f"{type(self).__name__}(n={n}, D={d}, dtype={self.dtype}, "
                f"host_bytes={self.nbytes})")


class HostStore(_HostTier):
    """Single host buffer holding the (n, D) full-precision rerank tier."""

    def __init__(self, x: np.ndarray):
        self.x = np.ascontiguousarray(np.asarray(x))
        if self.x.ndim != 2:
            raise ValueError(f"HostStore needs an (n, D) array, got shape "
                             f"{self.x.shape}")

    @property
    def shape(self) -> Tuple[int, int]:
        return self.x.shape

    @property
    def dtype(self):
        return self.x.dtype

    @property
    def nbytes(self) -> int:
        return self.x.nbytes

    def _materialize(self) -> np.ndarray:
        return self.x

    def gather_rows(self, ids) -> np.ndarray:
        """Host gather of rows by external id; -1 (padding) ids clamp to
        row 0 -- callers mask their scores, exactly like the device
        ``x_full[safe]`` gather."""
        ids = np.asarray(ids)
        return self.x[np.maximum(ids, 0)]

    # the per-query candidate fetch: the ONLY data that crosses host->HBM
    take = gather_rows

    def set_rows(self, ids, rows) -> "HostStore":
        new = self.x.copy()
        new[np.asarray(ids)] = np.asarray(rows, self.x.dtype)
        return HostStore(new)


class ShardedHostStore(_HostTier):
    """Spill-to-host rerank tier of a sharded placement: equal contiguous
    row shards as separate host buffers (shard s owns global rows
    [s * per, (s+1) * per)), mirroring ``ShardedIndex``'s row partition.
    ``take`` routes global candidate ids to their owning shard, so only
    each shard's kappa-row slice crosses the boundary."""

    def __init__(self, shards: Sequence[np.ndarray]):
        self.shards = tuple(np.ascontiguousarray(np.asarray(s))
                            for s in shards)
        if not self.shards:
            raise ValueError("ShardedHostStore needs >= 1 shard")
        per = {s.shape[0] for s in self.shards}
        dims = {s.shape[1:] for s in self.shards}
        if len(per) != 1 or len(dims) != 1:
            raise ValueError("shards must be equal contiguous row splits; "
                             f"got shapes {[s.shape for s in self.shards]}")
        self.per = self.shards[0].shape[0]

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.per * len(self.shards), self.shards[0].shape[1])

    @property
    def dtype(self):
        return self.shards[0].dtype

    @property
    def nbytes(self) -> int:
        return sum(s.nbytes for s in self.shards)

    def _materialize(self) -> np.ndarray:
        return np.concatenate(self.shards, axis=0)

    def gather_rows(self, ids) -> np.ndarray:
        ids = np.maximum(np.asarray(ids), 0)
        flat = ids.reshape(-1)
        out = np.empty((flat.size, self.shape[1]), self.dtype)
        owner = np.minimum(flat // self.per, self.n_shards - 1)
        for s, buf in enumerate(self.shards):
            sel = owner == s
            if sel.any():
                out[sel] = buf[flat[sel] - s * self.per]
        return out.reshape(ids.shape + (self.shape[1],))

    take = gather_rows

    def set_rows(self, ids, rows) -> "ShardedHostStore":
        ids = np.asarray(ids).reshape(-1)
        rows = np.asarray(rows, self.dtype).reshape(ids.size, -1)
        owner = np.minimum(ids // self.per, self.n_shards - 1)
        new = list(self.shards)
        for s in np.unique(owner):
            sel = owner == s
            buf = new[s].copy()
            buf[ids[sel] - s * self.per] = rows[sel]
            new[s] = buf
        return ShardedHostStore(new)


# Aux-only pytree registration: NO children. The store never appears in
# tree_leaves, so jit can't trace it, device transfers can't touch it, and
# the non-finite swap guard skips it (an O(n * D) host scan per swap would
# defeat the tier; the canary battery is the semantic guard). One
# consequence engines must handle: unflattening a jitted function's OUTPUT
# reattaches the TRACE-TIME aux object -- reattach the live store after
# every compiled call (``ServingEngine._reattach``).
for _cls in (HostStore, ShardedHostStore):
    jax.tree_util.register_pytree_node(
        _cls, lambda s: ((), s), lambda aux, children: aux)


def host_store(x) -> Optional[_HostTier]:
    """The host tier of an ``x_full``-like object, or None if device-
    resident."""
    return x if isinstance(x, _HostTier) else None


def demote(x_full, shards: int = 0) -> Union[HostStore, ShardedHostStore]:
    """Move a full-precision store to the host tier. ``shards > 0`` splits
    it into that many equal contiguous row shards (spill-to-host for
    sharded placements); rows must divide evenly, matching
    ``build_sharded_index``'s partition."""
    if isinstance(x_full, _HostTier):
        return x_full
    x = np.asarray(x_full)
    if shards:
        n = x.shape[0]
        if n % shards:
            raise ValueError(f"n={n} not divisible by shards={shards}")
        per = n // shards
        return ShardedHostStore([x[s * per:(s + 1) * per]
                                 for s in range(shards)])
    return HostStore(x)


def promote(x_full) -> jax.Array:
    """Inverse of :func:`demote`: materialize the store as a device array
    (used by offline/refit paths that genuinely need all n rows)."""
    return jnp.asarray(np.asarray(x_full))


def host_arrays(x_full) -> Optional[dict]:
    """Snapshot form of a host tier: a flat dict of numpy leaves the
    checkpoint machinery can persist WITHOUT routing them through device
    memory (None for device-resident stores -- their leaves ride the
    ServingState pytree as usual)."""
    store = host_store(x_full)
    if store is None:
        return None
    if isinstance(store, ShardedHostStore):
        return {f"shard{s}": buf for s, buf in enumerate(store.shards)}
    return {"x": store.x}


def from_host_arrays(arrays: dict) -> _HostTier:
    """Rebuild a host tier from its :func:`host_arrays` snapshot form."""
    if set(arrays) == {"x"}:
        return HostStore(arrays["x"])
    return ShardedHostStore([arrays[k] for k in sorted(
        arrays, key=lambda k: int(k.replace("shard", "")))])


def supports_pinned_host() -> bool:
    """Whether the default device exposes a ``pinned_host`` memory space
    (the memories-API形 of this tier: host-resident, device-addressable).
    TPU/GPU runtimes generally do; CPU backends report only
    ``unpinned_host`` -- their device memory IS host memory, so the
    two-stage pipeline's ``device_put`` is already a no-copy move."""
    kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    return "pinned_host" in kinds
