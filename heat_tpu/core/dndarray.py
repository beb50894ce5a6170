"""DNDarray — the distributed n-dimensional array.

TPU-native re-design of reference heat/core/dndarray.py. The reference pairs a
*local* ``torch.Tensor`` per MPI rank with global metadata
(dndarray.py:63-87) and hand-codes every global<->local translation
(getitem :652-908, resplit_ :1235-1357, redistribute_ :1029-1233, halos
:360-441). Here the payload is a single *global* ``jax.Array`` carrying a
``NamedSharding`` over the device mesh: global indexing, resharding and
collective insertion are XLA/GSPMD's job, so the thousand lines of index
translation disappear while the user-facing model — ``gshape`` + one ``split``
axis — stays identical.

Key semantic notes
------------------
* ``larray`` returns the **global logical** ``jax.Array`` (the natural JAX
  handle for local compute under SPMD). Per-device shards are exposed via
  ``lshards``/``lshape``/``lshape_map``.
* Arrays are always *balanced* in GSPMD's ceil-division layout; the
  reference's ragged ``lshape_map``/``balanced=False`` machinery
  (dndarray.py:57-60) intentionally does not exist (SURVEY.md §7 design
  stance). Global sizes not divisible by the mesh size are handled by
  **pad+mask**: the stored *physical* payload (``parray``) is zero-padded
  along the split axis to ``p * ceil(n/p)`` — a suffix of the global dim —
  so every device holds exactly one block-sized shard; ``gshape`` stays
  logical and ``larray`` slices the padding off. The reference instead
  carries ragged local chunks per rank (dndarray.py:57-60).
* "In-place" methods (``resplit_``, ``balance_``, ``__setitem__``) mutate the
  wrapper's handle to a new immutable ``jax.Array`` — aliasing differs from
  the reference (documented deviation).
* Under the eager fusion engine (``core/fusion.py``) the payload may
  transiently be a recorded-but-undispatched ``fusion.LazyArray`` expression
  chain; ``parray``/``larray`` are the forcing points that materialize it as
  one cached jitted program. No public API ever returns unmaterialized state.
"""

from __future__ import annotations

import functools
import operator
import warnings
from typing import Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import communication as comm_module
from . import devices, fusion, health_runtime, memledger, resilience, telemetry, types
from .communication import Communication, MeshCommunication
from .stride_tricks import sanitize_axis

__all__ = ["DNDarray"]

# forcing-point attribution scopes (telemetry): pushed only when a recorded
# chain is actually pending, so the non-lazy hot paths pay one isinstance
_T_LARRAY = telemetry.force_trigger("larray")
_T_INDEXING = telemetry.force_trigger("indexing")
_T_PYTREE = telemetry.force_trigger("pytree")
_T_COLLECTIVE = telemetry.force_trigger("collective")
_ITEM = operator.methodcaller("item")

Scalar = Union[int, float, bool, complex]


class LocalIndex:
    """Marker wrapper to index into the local shard (reference dndarray.py:34-48).

    Under the global-view runtime, indexing ``x.lloc[key]`` addresses the
    first addressable shard; provided for API parity.
    """

    def __init__(self, obj, key=None):
        self.obj = obj
        self.key = key

    def __getitem__(self, key):
        return self.obj[key]

    def __setitem__(self, key, value):
        self.obj[key] = value


class DNDarray:
    """Distributed N-Dimensional array backed by a sharded global ``jax.Array``.

    Parameters
    ----------
    array : jax.Array
        Global payload (already placed under the intended sharding).
    gshape : tuple of int
        Global shape (must equal ``array.shape``).
    dtype : heat_tpu.core.types.datatype
        Element type class.
    split : int or None
        The single distribution axis, or None for replicated.
    device : heat_tpu.core.devices.Device
    comm : MeshCommunication
    balanced : bool
        Always True in this runtime; kept for API parity.
    """

    def __init__(
        self,
        array: jax.Array,
        gshape: Tuple[int, ...],
        dtype,
        split: Optional[int],
        device,
        comm: Communication,
        balanced: bool = True,
    ):
        self.__gshape = tuple(int(s) for s in gshape)
        self.__dtype = dtype
        self.__split = split
        self.__device = device
        self.__comm = comm
        self.__balanced = True
        # pad+mask for ragged splits: if the (logical) payload's split dim is
        # not divisible by the mesh size, physically pad it to p*ceil(n/p) and
        # shard — every device then holds one block-sized shard instead of a
        # full replica (reference carries ragged chunks per rank,
        # dndarray.py:57-60; SURVEY.md §7 prescribes pad+mask on TPU).
        # Payloads arriving already at the padded physical shape (internal
        # reconstructions, e.g. astype) are stored as-is.
        if (
            split is not None
            and isinstance(array, jax.Array)
            and array.ndim > 0
            and split < array.ndim
            and tuple(array.shape) == self.__gshape
            and comm is not None
            and self.__gshape[split] % comm.size != 0
        ):
            array = _pad_and_place(array, split, comm)
        self.__array = array
        if isinstance(array, jax.Array):
            # live-buffer ledger attribution (core/memledger.py): wrapper
            # payloads are the "dndarray" owner class
            memledger.tag(array, "dndarray")

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def balanced(self) -> bool:
        """Arrays are always balanced under GSPMD (reference dndarray.py:160)."""
        return True

    @property
    def comm(self) -> MeshCommunication:
        return self.__comm

    @property
    def device(self):
        return self.__device

    @property
    def dtype(self):
        return self.__dtype

    @property
    def gshape(self) -> Tuple[int, ...]:
        return self.__gshape

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.__gshape

    @property
    def ndim(self) -> int:
        return len(self.__gshape)

    @property
    def size(self) -> int:
        return int(np.prod(self.__gshape, dtype=np.int64)) if self.__gshape else 1

    gnumel = size

    @property
    def lnumel(self) -> int:
        return int(np.prod(self.lshape, dtype=np.int64))

    @property
    def nbytes(self) -> int:
        return self.size * np.dtype(self.__dtype.jax_type()).itemsize

    gnbytes = nbytes

    @property
    def lnbytes(self) -> int:
        return self.lnumel * np.dtype(self.__dtype.jax_type()).itemsize

    @property
    def padded(self) -> bool:
        """True when the physical payload carries suffix padding along the
        split axis (ragged global size, see module docstring)."""
        s = self.__split
        return s is not None and s < self.__array.ndim and (
            int(self.__array.shape[s]) != self.__gshape[s]
        )

    @property
    def _payload(self):
        """Internal: the raw stored payload WITHOUT forcing — a ``jax.Array``
        or, while a recorded op chain is pending, a ``fusion.LazyArray``.
        Only the fusion recorder should consume this; everything else goes
        through :attr:`parray`/:attr:`larray`, which force."""
        return self.__array

    @property
    def parray(self) -> jax.Array:
        """The *physical* payload: the stored ``jax.Array``, zero-padded along
        the split axis to ``p * ceil(n/p)`` when the global size is ragged.
        Pad-aware fast paths (elementwise engines, shard_map kernels) may
        compute on it directly; the padding region's content is unspecified.

        FORCING POINT: a pending recorded op chain (``fusion.LazyArray``
        payload) is materialized here as one cached jitted program and the
        result is placed under the split sharding; every payload consumer
        (``larray``, ``numpy()``, indexing, printing, I/O, collectives,
        linalg, the eager engine fallbacks) funnels through this property."""
        arr = self.__array
        if isinstance(arr, fusion.LazyArray):
            lazy = arr
            arr = fusion.force(arr)
            if isinstance(arr, jax.core.Tracer):
                # forced inside an enclosing trace: the value belongs to that
                # trace — hand it over but never store it on the wrapper
                return arr
            # heat.place, while tracing: errstate check, placement, re-tag
            span = telemetry.Phases("heat.place", cid=lazy.cid) if telemetry.tracing() else None
            try:
                split = self.__split
                if split is not None and (arr.ndim == 0 or split >= arr.ndim):
                    split = None
                if resilience._ERRSTATE is not None or resilience._TLS_ARMED:
                    # numeric error policy at the forcing seam, on the LOGICAL
                    # extent only: the padding suffix of a ragged split holds
                    # unspecified garbage (log(0) = -inf) and must not be
                    # checked. A raise leaves the wrapper unforced (the cached
                    # program makes re-forcing under "ignore" cheap).
                    check_val = arr
                    if split is not None and int(arr.shape[split]) != self.__gshape[split]:
                        idx = [slice(None)] * arr.ndim
                        idx[split] = slice(0, self.__gshape[split])
                        check_val = arr[tuple(idx)]
                    # provenance: the fused program key stamped on the root at
                    # force time + the chain's correlation id — a nonfinite
                    # finding names its producer, not just the catch point
                    resilience.check_nonfinite(
                        check_val, "force",
                        program=getattr(lazy, "program", None), cid=lazy.cid,
                    )
                arr = _ensure_split(arr, split, self.__comm)
                self.__array = arr
                # re-attribute the forced value: the async future ("fusion")
                # has been claimed by this wrapper
                memledger.tag(arr, "dndarray")
            finally:
                if span is not None:
                    fusion.note_phase("place", span.close())
        return arr

    def _force_payload(self, scope) -> jax.Array:
        """:attr:`parray` with the forcing point attributed to ``scope`` when
        a recorded chain is pending (telemetry forcing-point attribution; the
        outermost scope wins, so e.g. print-over-larray reads as print)."""
        if isinstance(self.__array, fusion.LazyArray):
            with scope:
                return self.parray
        return self.parray

    def _note_blocking_sync(self, kind: str):
        """Telemetry seam for host boundaries (``item``/``numpy``/shard
        reads): counted as a *blocking sync* only when a pending recorded
        chain must be materialized synchronously here — reading a value whose
        program is already dispatched (async forcing) is free and does not
        count. One isinstance on the disabled path.

        Carries the pending chain's correlation id into the trace timeline
        and returns the (verbose-mode) timeline event so the call site can
        close it via ``telemetry.end_blocking_sync`` once the host holds the
        value — the exported trace then shows the sync's true wall duration."""
        if telemetry._MODE:
            arr = self.__array
            if isinstance(arr, fusion.LazyArray) and arr._value is None:
                return telemetry.record_blocking_sync(kind, cid=arr.cid)
        return None

    def _host_read(self, kind: str, fetch):
        """``fetch(self.larray)``, the blocking device-to-host read of
        ``item()``/``numpy()``. The payload is forced first, so that while
        tracing the read is a ``heat.read`` span (and ``phase_read_ns``) of
        its own, after and never around ``heat.force``, with two children
        side by side: ``.ready`` (the wait until the device has made the
        payload) and ``.copy`` (the fetch of what is ready), which add to
        ``phase_read_ready_ns`` and ``phase_read_copy_ns``."""
        cid = getattr(self.__array, "cid", 0)  # the pending chain's, else 0
        arr = self.larray
        if not telemetry.tracing():
            return fetch(arr)
        span = telemetry.Phases("heat.read", cid=cid, kind=kind)
        try:
            return telemetry.ready_then(span.phase, arr, fetch)
        finally:
            fusion.note_phase("read", span.close(), span.ns)

    @property
    def larray(self) -> jax.Array:
        """The **logical** global ``jax.Array`` (see module docstring): the
        physical payload with any split-axis suffix padding sliced off.
        Forces a pending recorded chain (see :attr:`parray`)."""
        arr = self._force_payload(_T_LARRAY)
        if not self.padded:
            return arr
        idx = [slice(None)] * arr.ndim
        idx[self.__split] = slice(0, self.__gshape[self.__split])
        return arr[tuple(idx)]

    @larray.setter
    def larray(self, array: jax.Array):
        """Replace the payload with a new **logical** array (reference
        dndarray.py:229-247); shape/dtype metadata is re-derived and ragged
        splits are re-padded."""
        if not isinstance(array, jax.Array):
            raise TypeError(f"larray must be a jax.Array, got {type(array)}")
        self.__gshape = tuple(int(s) for s in array.shape)
        self.__dtype = types.canonical_heat_type(array.dtype)
        split = self.__split
        if split is not None and (array.ndim == 0 or split >= array.ndim):
            self.__split = split = None
        if split is not None and self.__gshape[split] % self.__comm.size != 0:
            array = _pad_and_place(array, split, self.__comm)
        self.__array = array
        memledger.tag(array, "dndarray")

    def _replace(
        self, array: jax.Array, split: Optional[int], gshape: Optional[Tuple[int, ...]] = None
    ) -> "DNDarray":
        """Internal: swap payload AND split metadata consistently (used by the
        op engines' ``out=`` paths). With ``gshape`` given, ``array`` is taken
        as the physical (possibly padded) payload for that logical shape."""
        self.__split = split
        if gshape is not None:
            gshape = tuple(int(s) for s in gshape)
            expected = list(gshape)
            if split is not None and split < len(expected):
                p = self.__comm.size
                n = expected[split]
                expected[split] = (-(-n // p) if n else 0) * p
            if tuple(array.shape) not in (gshape, tuple(expected)):
                raise ValueError(
                    f"physical payload shape {tuple(array.shape)} matches neither the "
                    f"logical shape {gshape} nor its padded form {tuple(expected)}"
                )
            self.__array = array
            self.__gshape = gshape
            self.__dtype = types.canonical_heat_type(array.dtype)
            memledger.tag(array, "dndarray")
        else:
            self.larray = array
        return self

    def _adopt(self, other: "DNDarray") -> "DNDarray":
        """Internal ``out=`` seam, the deferred form of ``_replace``: take
        ``other``'s payload and metadata WITHOUT forcing — a pending recorded
        chain stays pending and this wrapper becomes its async-forcing root.
        Concrete payloads route through ``_replace`` (identical semantics)."""
        payload = other._payload
        if isinstance(payload, fusion.LazyArray) and payload._value is None:
            self.__gshape = other.gshape
            self.__dtype = other.dtype
            self.__split = other.split
            self.__array = payload
            fusion.register_root(self)
            return self
        return self._replace(other.parray, other.split, gshape=other.gshape)

    @property
    def lshards(self) -> List[np.ndarray]:
        """Per-device **logical** local shards (host copies), in device order:
        each physical shard with its padding rows sliced off (tail devices of
        a ragged split may hold empty logical shards)."""
        self._note_blocking_sync("shards")
        phys = self.parray
        if not self.padded:
            return [np.asarray(s.data) for s in phys.addressable_shards]
        split = self.__split
        counts, _ = self.__comm.counts_displs_shape(self.__gshape, split)
        block = int(phys.shape[split]) // self.__comm.size
        out = []
        for s in phys.addressable_shards:
            start = s.index[split].start or 0
            rank = start // block if block else 0
            idx = [slice(None)] * self.__array.ndim
            idx[split] = slice(0, counts[rank])
            out.append(np.asarray(s.data[tuple(idx)]))
        return out

    def ranked_shards(self):
        """Yield ``(rank, block)`` for every shard THIS process addresses, in
        mesh-rank order; each block is the shard's **logical** extent as a
        host numpy array (physical split-axis padding trimmed — pad+mask
        contract). Ragged-tail ranks whose logical count is zero are skipped;
        a replicated / 0-d array yields the single pair ``(0, full array)``.

        This is the shard/stream protocol shared by the streaming file
        writers (``core/io.py`` — HDF5 hyperslabs, CSV rows, npy buffers) and
        the sharded checkpoint writer (``utils/checkpoint.py``): one host
        transfer per block, never a global gather. Forces a pending recorded
        chain (see :attr:`parray`)."""
        self._note_blocking_sync("shards")
        split = self.__split
        if split is None or self.ndim == 0:
            yield 0, np.asarray(self.larray)  # local payload, not a gather
            return
        counts, _ = self.__comm.counts_displs_shape(self.__gshape, split)
        phys = self.parray
        block = int(phys.shape[split]) // self.__comm.size
        shards = sorted(phys.addressable_shards, key=lambda s: s.index[split].start or 0)
        for s in shards:
            r = (s.index[split].start or 0) // block if block else 0
            c = counts[r]
            if c:
                idx = [slice(None)] * self.ndim
                idx[split] = slice(0, c)
                yield r, np.asarray(s.data[tuple(idx)])

    @property
    def lshape(self) -> Tuple[int, ...]:
        """Logical shape of this process's representative device shard
        (reference dndarray.py:301 reports the calling rank's local tensor;
        the analog under one controller per host is the first rank THIS
        process addresses — multihost.representative_rank — so every host
        reports a shard it actually holds; contract in
        doc/internals_distribution.md)."""
        from .multihost import representative_rank

        rank = representative_rank(self.__comm.devices)
        _, lshape, _ = self.__comm.chunk(self.__gshape, self.__split, rank=rank)
        return lshape

    @property
    def lshape_map(self):
        """(n_devices, ndim) map of shard shapes (reference dndarray.py:569-600:
        collective metadata exchange; here deterministic arithmetic)."""
        from . import factories

        lmap = self.__comm.lshape_map(self.__gshape, self.__split)
        return factories.array(lmap, dtype=types.int64, device=self.__device, comm=self.__comm)

    @property
    def split(self) -> Optional[int]:
        return self.__split

    @property
    def stride(self) -> Tuple[int, ...]:
        """Strides in elements, C-order (reference dndarray.py:321)."""
        strides = []
        acc = 1
        for s in reversed(self.__gshape):
            strides.append(acc)
            acc *= int(s)
        return tuple(reversed(strides))

    @property
    def strides(self) -> Tuple[int, ...]:
        """Strides in bytes (reference dndarray.py:330)."""
        item = np.dtype(self.__dtype.jax_type()).itemsize
        return tuple(s * item for s in self.stride)

    @property
    def T(self) -> "DNDarray":
        from .linalg import basics

        return basics.transpose(self, None)

    @property
    def real(self) -> "DNDarray":
        from . import complex_math

        return complex_math.real(self)

    @property
    def imag(self) -> "DNDarray":
        from . import complex_math

        return complex_math.imag(self)

    @property
    def lloc(self) -> LocalIndex:
        return LocalIndex(self)

    # ------------------------------------------------------------------
    # distribution management
    # ------------------------------------------------------------------
    def is_distributed(self) -> bool:
        """True if data lives on more than one device (reference dndarray.py:957)."""
        return self.__split is not None and self.__comm.is_distributed()

    def is_balanced(self, force_check: bool = False) -> bool:
        return True

    def balance_(self) -> "DNDarray":
        """No-op: GSPMD keeps arrays balanced (reference dndarray.py:470-508)."""
        return self

    def counts_displs(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """Counts/displacements along the split axis (reference dndarray.py:543)."""
        if self.__split is None:
            raise ValueError("Non-distributed DNDarray has no counts and displacements")
        return self.__comm.counts_displs_shape(self.__gshape, self.__split)

    def resplit_(self, axis: Optional[int] = None) -> "DNDarray":
        """In-place redistribution to a new split axis (reference
        dndarray.py:1235-1357: Allgatherv / tile-P2P; here one ``device_put``
        whose resharding collectives XLA chooses).

        Under collective-aware fusion a PENDING recorded chain stays
        recorded: the redistribution becomes a collective node in the DAG
        (``fusion.defer_reshard`` — a sharding constraint the fused
        program's partitioner schedules), so chains spanning a resplit
        compile into one program instead of fencing here. The
        ``collective.reshard`` fault site still fires at record-or-dispatch
        time, before any metadata mutates; ``HEAT_TPU_FUSION_COLLECTIVES=0``
        restores the force-at-collective behavior."""
        axis = sanitize_axis(self.__gshape, axis)
        if axis == self.__split:
            return self
        was_padded = self.padded
        if resilience._ARMED:
            # a preemption mid-redistribution is a classic pod failure mode;
            # the site lets tests prove it surfaces BEFORE the wrapper's
            # metadata is mutated (no half-resharded state)
            resilience.check("collective.reshard")
        payload = self.__array
        if (
            isinstance(payload, fusion.LazyArray)
            and payload._value is None
            and fusion.collectives_active()
        ):
            node = fusion.defer_reshard(
                payload, self.__gshape, self.__split, was_padded, axis, self.__comm
            )
            if node is not None:
                self.__split = axis
                self.__array = node
                fusion.register_root(self)
                return self
            # recording declined (defer_reshard left the breadcrumb): force
            # and reshard eagerly below — today's behavior
        self._force_payload(_T_COLLECTIVE)  # redistribution = collective
        logical = self.larray
        self.__split = axis
        if axis is not None and self.__gshape[axis] % self.__comm.size != 0:
            self.__array = _pad_and_place(logical, axis, self.__comm)
        elif was_padded:
            # the old payload was padded, so ``logical`` is a fresh slice no
            # caller can hold — donate its buffer to the reshard program
            self.__array = _reshard_donating(logical, axis, self.__comm)
        else:
            self.__array = _ensure_split(logical, axis, self.__comm)
        return self

    def redistribute_(self, lshape_map=None, target_map=None) -> "DNDarray":
        """Reference dndarray.py:1029-1233 moves data to an arbitrary ragged
        target map. GSPMD owns the (always-balanced) layout, so only the
        balanced identity map is representable; anything else is rejected."""
        if target_map is not None:
            tm = np.asarray(target_map.larray if isinstance(target_map, DNDarray) else target_map)
            if not np.array_equal(tm, self.__comm.lshape_map(self.__gshape, self.__split)):
                raise NotImplementedError(
                    "arbitrary (ragged) target maps are not representable under GSPMD; "
                    "arrays are always balanced (SURVEY.md §7 design stance)"
                )
        return self

    def get_halo(self, halo_size: int) -> None:
        """Materialize split-axis boundary halos from neighbor devices
        (reference dndarray.py:360-441: Isend/Irecv to split-axis neighbors).

        The TPU rendering is one ``shard_map`` program with two
        ``ppermute`` ring shifts: every device sends its trailing
        ``halo_size`` slice to the next device and its leading slice to the
        previous one; edge devices receive zeros. The received halos are
        cached and consumed by :attr:`array_with_halos` (used by the
        distributed ``convolve`` stencil path, signal.py)."""
        if not isinstance(halo_size, int):
            raise TypeError(f"halo_size needs to be of Python type integer, {type(halo_size)} given")
        if halo_size < 0:
            raise ValueError(f"halo_size needs to be a positive Python integer, {halo_size} given")
        self.__halo_size = halo_size
        self.__halo_cache = None
        if halo_size > 0 and self.__split is not None and self.__comm.size > 1:
            split = self.__split
            p = self.__comm.size
            payload = self.__array
            if (
                isinstance(payload, fusion.LazyArray)
                and payload._value is None
                and fusion.collectives_active()
                and not self.padded
            ):
                # deferred exchange: the ppermute pair records as one
                # multi-output collective node consumed lazily (convolve's
                # stencil path compiles exchange + conv into ONE program);
                # the public array_with_halos still materializes
                block = int(payload.shape[split]) // p
                if 0 < halo_size <= block:
                    if resilience._ARMED:
                        resilience.check("collective.halo")
                    kernel = _halo_exchange_kernel(
                        self.__comm.axis_name, split, halo_size, block, p
                    )
                    nodes = fusion.defer_apply(
                        self.__comm, kernel, (self,),
                        in_splits=(split,), out_split=(split, split),
                    )
                    if nodes is not None:
                        hshape = list(payload.shape)
                        hshape[split] = halo_size * p
                        self.__halo_cache = (
                            fusion.wrap_node(nodes[0], tuple(hshape), split, self),
                            fusion.wrap_node(nodes[1], tuple(hshape), split, self),
                        )
                        return
                else:
                    return  # halo wider than a block: no exchange either way
            phys = self._force_payload(_T_COLLECTIVE)
            block = int(phys.shape[split]) // p
            if 0 < halo_size <= block:
                if resilience._ARMED:
                    resilience.check("collective.halo")
                fn = _halo_program(
                    self.__comm.mesh,
                    self.__comm.axis_name,
                    split,
                    halo_size,
                    tuple(int(s) for s in phys.shape),
                    str(phys.dtype),
                )
                self.__halo_cache = fn(phys)

    @property
    def array_with_halos(self) -> jax.Array:
        """The physical payload with each device's shard extended by the
        halos exchanged in :meth:`get_halo` (reference dndarray.py:332-341):
        a global array of shape ``p * (block + 2*halo)`` along the split axis
        where every device holds ``[from_prev | local | from_next]``. Without
        materialized halos this is the logical global view."""
        halos = getattr(self, "_DNDarray__halo_cache", None)
        if halos is None:
            return self.larray
        from_prev, from_next = halos
        # the payload must land BEFORE the halo wrappers force: the deferred
        # exchange's parent consumes this chain, so forcing it first makes
        # the chain a leaf of the exchange program instead of a recompute
        phys = self.parray
        if isinstance(from_prev, DNDarray):
            # deferred exchange: the PUBLIC property still returns a
            # materialized array (tests pin np.asarray/.shape on it); the
            # lazy consumer seam is _halo_wrappers (signal.convolve)
            from_prev = from_prev._force_payload(_T_COLLECTIVE)
            from_next = from_next._force_payload(_T_COLLECTIVE)
        fn = _halo_concat_program(
            self.__comm.mesh,
            self.__comm.axis_name,
            self.__split,
            tuple(int(s) for s in phys.shape),
            tuple(int(s) for s in from_prev.shape),
            str(phys.dtype),
        )
        return fn(from_prev, phys, from_next)

    def _halo_wrappers(self) -> Optional[tuple]:
        """Internal: the deferred ``(from_prev, from_next)`` halo pair as
        pending DNDarray wrappers — the lazy seam ``signal.convolve`` records
        its stencil against so exchange + conv compile into one program.
        None when :meth:`get_halo` ran eagerly (or found nothing to do)."""
        halos = getattr(self, "_DNDarray__halo_cache", None)
        if halos is not None and isinstance(halos[0], DNDarray):
            return halos
        return None

    @property
    def halo_prev(self) -> Optional[jax.Array]:
        """Boundary slice a previous-neighbor shard would send (reference
        dndarray.py:312-320). Derived from the global view: the trailing
        ``halo_size`` slice along the split axis of the rank-0 shard."""
        hs = getattr(self, "_DNDarray__halo_size", None)
        if not hs or self.__split is None or self.__comm.size < 2:
            return None
        _, _, slices = self.__comm.chunk(self.__gshape, self.__split, rank=0)
        stop = slices[self.__split].stop
        idx = [slice(None)] * len(self.__gshape)
        idx[self.__split] = slice(max(stop - hs, 0), stop)
        return self.larray[tuple(idx)]

    @property
    def halo_next(self) -> Optional[jax.Array]:
        """Boundary slice a next-neighbor shard would send (reference
        dndarray.py:322-330); leading ``halo_size`` slice of the rank-1 shard."""
        hs = getattr(self, "_DNDarray__halo_size", None)
        if not hs or self.__split is None or self.__comm.size < 2:
            return None
        _, _, slices = self.__comm.chunk(self.__gshape, self.__split, rank=1)
        start = slices[self.__split].start
        idx = [slice(None)] * len(self.__gshape)
        idx[self.__split] = slice(start, start + hs)
        return self.larray[tuple(idx)]

    def create_lshape_map(self, force_check: bool = False):
        """Method form of ``lshape_map`` (reference dndarray.py:569-600)."""
        return self.lshape_map

    # ------------------------------------------------------------------
    # conversions
    # ------------------------------------------------------------------
    def astype(self, dtype, copy: bool = True) -> "DNDarray":
        """Cast to a new element type (reference dndarray.py:443-468). Casts
        of a pending recorded chain stay recorded (``fusion.cast`` node)."""
        dtype = types.canonical_heat_type(dtype)
        arr = self.__array
        if isinstance(arr, fusion.LazyArray):
            try:
                casted = fusion.cast(arr, dtype.jax_type())
            except Exception as exc:  # same ONE policy as the defer_* sites
                if not resilience.record_recoverable(exc):
                    raise
                # recording the cast failed: force the chain and cast eagerly
                casted = self.parray.astype(dtype.jax_type())
        else:
            casted = arr.astype(dtype.jax_type())
        if copy:
            out = DNDarray(
                casted, self.__gshape, dtype, self.__split, self.__device, self.__comm
            )
            if isinstance(casted, fusion.LazyArray):
                fusion.register_root(out)  # async-forcing batch candidate
            return out
        self.__array = casted
        self.__dtype = dtype
        if isinstance(casted, fusion.LazyArray):
            fusion.register_root(self)
        return self

    def numpy(self) -> np.ndarray:
        """Gather the global (logical) array to host numpy (reference
        dndarray.py:991-1003); padding never leaves the device."""
        token = self._note_blocking_sync("numpy")
        with health_runtime.watch(
            "sync:numpy", cid=None if token is None else token.get("cid")
        ):
            out = np.asarray(self._host_read("numpy", jax.device_get))
        telemetry.end_blocking_sync(token)
        return out

    def __array__(self, dtype=None) -> np.ndarray:
        out = self.numpy()
        return out.astype(dtype) if dtype is not None else out

    def item(self):
        """The single scalar value (reference dndarray.py:965)."""
        if self.size != 1:
            raise ValueError("only one-element DNDarrays can be converted to Python scalars")
        token = self._note_blocking_sync("item")
        with health_runtime.watch(
            "sync:item", cid=None if token is None else token.get("cid")
        ):
            out = self._host_read("item", _ITEM)
        telemetry.end_blocking_sync(token)
        return out

    def tolist(self, keepsplit: bool = False) -> list:
        return self.numpy().tolist()

    def cpu(self) -> "DNDarray":
        """Copy to the CPU backend (reference dndarray.py:510)."""
        return self._to_device(devices.cpu)

    def tpu(self) -> "DNDarray":
        return self._to_device(devices.tpu)

    gpu = tpu

    def _to_device(self, device) -> "DNDarray":
        device = devices.sanitize_device(device)
        if device == self.__device:
            return self
        comm = MeshCommunication(jax.devices(device.device_type))
        arr = _ensure_split(jnp.asarray(self.numpy()), self.__split, comm)
        return DNDarray(arr, self.__gshape, self.__dtype, self.__split, device, comm)

    # ------------------------------------------------------------------
    # scalar dunder conversions (reference dndarray.py:516-540)
    # ------------------------------------------------------------------
    def __bool__(self) -> bool:
        return bool(self.item())

    def __int__(self) -> int:
        return int(self.item())

    def __float__(self) -> float:
        return float(self.item())

    def __complex__(self) -> complex:
        return complex(self.item())

    def __index__(self) -> int:
        return int(self.item())

    def __len__(self) -> int:
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.__gshape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ------------------------------------------------------------------
    # indexing — global semantics via jax; split bookkeeping simplified
    # (reference dndarray.py:652-908 / 1359-1648 does manual global->local
    # translation; GSPMD makes global indexing native)
    # ------------------------------------------------------------------
    @staticmethod
    def _unwrap_key(key):
        if isinstance(key, DNDarray):
            return key.larray
        if isinstance(key, tuple):
            return tuple(DNDarray._unwrap_key(k) for k in key)
        if isinstance(key, list):
            # numpy fancy-index semantics: a list key is an array index
            # (jax rejects bare sequences, jax#4564); empty lists must be
            # integer-typed or jax rejects the float indexer
            if not key:
                return jnp.asarray([], dtype=jnp.int32)
            return jnp.asarray([DNDarray._unwrap_key(k) for k in key])
        if isinstance(key, np.ndarray):
            return jnp.asarray(key)
        return key

    def _result_split(self, key) -> Optional[int]:
        """Split of an indexing result: follow what happens to the split dim.

        Advanced (boolean-mask / integer-array) keys keep the distribution
        (reference dndarray.py:652-908 translates them globally; here the
        gather output is re-constrained to ``split``): with a single advanced
        key the result's advanced block lands in place — if it consumed the
        split dimension the result is split along the block's first output
        dim, otherwise the split dim's new position is tracked through the
        key. Multiple advanced keys (numpy moves the block to the front, and
        combining them permutes data across devices unpredictably) degrade to
        replicated.
        """
        if self.__split is None:
            return None
        key_t = key if isinstance(key, tuple) else (key,)
        # expand Ellipsis
        if any(k is Ellipsis for k in key_t):
            n_explicit = 0
            for k in key_t:
                if k is Ellipsis or k is None:
                    continue
                if _is_advanced_key(k) and _key_dtype_is_bool(k):
                    n_explicit += _key_ndim(k)
                else:
                    n_explicit += 1
            expanded: list = []
            for k in key_t:
                if k is Ellipsis:
                    expanded.extend([slice(None)] * (self.ndim - n_explicit))
                else:
                    expanded.append(k)
            key_t = tuple(expanded)

        advanced = [k for k in key_t if _is_advanced_key(k)]
        if len(advanced) > 1:
            return None  # numpy front-moves the block; distribution undefined
        out_dim = 0
        in_dim = 0
        for k in key_t:
            if k is None:
                out_dim += 1
                continue
            if _is_advanced_key(k):
                is_bool = _key_dtype_is_bool(k)
                consumed = _key_ndim(k) if is_bool else 1
                produced = 1 if is_bool else _key_ndim(k)
                if in_dim <= self.__split < in_dim + consumed:
                    # the advanced block consumed the split dim: shard the
                    # block's first result dim (0-D int keys drop the dim)
                    return out_dim if produced > 0 else None
                in_dim += consumed
                out_dim += produced
                continue
            if in_dim == self.__split:
                return out_dim if isinstance(k, slice) else None
            if isinstance(k, (int, np.integer)):
                in_dim += 1
            else:  # slice
                in_dim += 1
                out_dim += 1
        # split dim untouched by the key: shift by dropped/inserted dims before it
        return out_dim + (self.__split - in_dim)

    def __getitem__(self, key) -> "DNDarray":
        self._force_payload(_T_INDEXING)
        jkey = DNDarray._unwrap_key(key)
        result = self.larray[jkey]
        split = self._result_split(key) if result.ndim > 0 else None
        if split is not None and split >= result.ndim:
            split = None
        arr = _ensure_split(result, split, self.__comm)
        return DNDarray(
            arr,
            tuple(result.shape),
            types.canonical_heat_type(result.dtype),
            split,
            self.__device,
            self.__comm,
        )

    def __setitem__(self, key, value):
        self._force_payload(_T_INDEXING)
        jkey = DNDarray._unwrap_key(key)
        if isinstance(value, DNDarray):
            value = value.larray
        # numpy setitem semantics: the value is cast to the destination dtype
        if hasattr(value, "dtype") and value.dtype != self.__array.dtype:
            value = jnp.asarray(value).astype(self.__array.dtype)
        new = self.larray.at[jkey].set(value)
        if self.padded:
            self.__array = _pad_and_place(new, self.__split, self.__comm)
        else:
            # ``new`` is a freshly-computed temporary: donate it on reshard
            self.__array = _reshard_donating(new, self.__split, self.__comm)

    def fill_diagonal(self, value) -> "DNDarray":
        """Fill the main diagonal in place (reference dndarray.py:608-650)."""
        if self.ndim != 2:
            raise ValueError("Only 2D tensors supported")
        n = min(self.__gshape)
        idx = jnp.arange(n)
        new = self.larray.at[idx, idx].set(value)
        if self.padded:
            self.__array = _pad_and_place(new, self.__split, self.__comm)
        else:
            self.__array = _reshard_donating(new, self.__split, self.__comm)
        return self

    # ------------------------------------------------------------------
    # operator protocol — delegates to the operator library, mirroring the
    # reference's pattern of module-level functions bound as methods
    # ------------------------------------------------------------------
    def __add__(self, other):
        from . import arithmetics

        return arithmetics.add(self, other)

    def __radd__(self, other):
        from . import arithmetics

        return arithmetics.add(self, other)

    def __sub__(self, other):
        from . import arithmetics

        return arithmetics.sub(self, other)

    def __rsub__(self, other):
        from . import arithmetics

        return arithmetics.sub(other, self)

    def __mul__(self, other):
        from . import arithmetics

        return arithmetics.mul(self, other)

    def __rmul__(self, other):
        from . import arithmetics

        return arithmetics.mul(self, other)

    def __truediv__(self, other):
        from . import arithmetics

        return arithmetics.div(self, other)

    def __rtruediv__(self, other):
        from . import arithmetics

        return arithmetics.div(other, self)

    def __floordiv__(self, other):
        from . import arithmetics

        return arithmetics.floordiv(self, other)

    def __rfloordiv__(self, other):
        from . import arithmetics

        return arithmetics.floordiv(other, self)

    def __mod__(self, other):
        from . import arithmetics

        return arithmetics.mod(self, other)

    def __rmod__(self, other):
        from . import arithmetics

        return arithmetics.mod(other, self)

    def __pow__(self, other):
        from . import arithmetics

        return arithmetics.pow(self, other)

    def __rpow__(self, other):
        from . import arithmetics

        return arithmetics.pow(other, self)

    def __matmul__(self, other):
        from .linalg import basics

        return basics.matmul(self, other)

    def __and__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_and(self, other)

    def __or__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_or(self, other)

    def __xor__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_xor(self, other)

    def __lshift__(self, other):
        from . import arithmetics

        return arithmetics.left_shift(self, other)

    def __rshift__(self, other):
        from . import arithmetics

        return arithmetics.right_shift(self, other)

    def __invert__(self):
        from . import arithmetics

        return arithmetics.invert(self)

    def __neg__(self):
        from . import arithmetics

        return arithmetics.neg(self)

    def __pos__(self):
        from . import arithmetics

        return arithmetics.pos(self)

    def __abs__(self):
        from . import rounding

        return rounding.abs(self)

    def __eq__(self, other):  # type: ignore[override]
        from . import relational

        return relational.eq(self, other)

    def __ne__(self, other):  # type: ignore[override]
        from . import relational

        return relational.ne(self, other)

    def __lt__(self, other):
        from . import relational

        return relational.lt(self, other)

    def __le__(self, other):
        from . import relational

        return relational.le(self, other)

    def __gt__(self, other):
        from . import relational

        return relational.gt(self, other)

    def __ge__(self, other):
        from . import relational

        return relational.ge(self, other)

    __hash__ = None  # type: ignore[assignment]

    # ------------------------------------------------------------------
    # pytree protocol — beyond the reference (which is eager-only)
    # ------------------------------------------------------------------
    def _tree_flatten(self):
        """Flatten to (physical payload, static metadata).

        Registering DNDarray as a pytree makes whole ``ht.*`` pipelines
        compilable with plain ``jax.jit`` (and differentiable with
        ``jax.grad``): the payload becomes the traced leaf while
        gshape/dtype/split stay static aux data. Eager per-op dispatch —
        the reference's only execution model, and most of the wall time of
        small ops (one host dispatch per op) — then collapses into one XLA
        program per pipeline.

        FORCING POINT: a pending recorded chain materializes here, so the
        enclosing trace sees a concrete (or tracer) leaf, never a LazyArray.
        """
        aux = (self.__gshape, self.__dtype, self.__split, self.__device, self.__comm)
        return (self._force_payload(_T_PYTREE),), aux

    @classmethod
    def _tree_unflatten(cls, aux, children):
        """Rebuild from :meth:`_tree_flatten` parts WITHOUT re-deriving
        anything: the payload may be a tracer (under jit) or a sentinel
        (tree_structure probes), so it must not be inspected; it is stored
        at whatever (possibly padded physical) shape it carries."""
        (payload,) = children
        obj = cls.__new__(cls)
        (
            obj._DNDarray__gshape,
            obj._DNDarray__dtype,
            obj._DNDarray__split,
            obj._DNDarray__device,
            obj._DNDarray__comm,
        ) = aux
        obj._DNDarray__balanced = True
        obj._DNDarray__array = payload
        return obj

    # ------------------------------------------------------------------
    # printing (reference heat/core/printing.py)
    # ------------------------------------------------------------------
    def __repr__(self) -> str:
        from . import printing

        return printing.__str__(self)

    __str__ = __repr__


def _is_advanced_key(k) -> bool:
    """True for boolean-mask / integer-array index components (DNDarray,
    numpy / jax arrays, or list keys — numpy fancy-index semantics)."""
    return isinstance(k, (list, np.ndarray, jax.Array)) or isinstance(k, DNDarray)


def _key_dtype_is_bool(k) -> bool:
    if isinstance(k, DNDarray):
        return k.larray.dtype == jnp.bool_
    if isinstance(k, list):
        return len(k) > 0 and isinstance(k[0], (bool, np.bool_))
    return np.asarray(k).dtype == np.bool_ if isinstance(k, np.ndarray) else k.dtype == jnp.bool_


def _key_ndim(k) -> int:
    if isinstance(k, DNDarray):
        return k.ndim
    if isinstance(k, list):
        return np.asarray(k).ndim
    return k.ndim


@functools.lru_cache(maxsize=None)
def _halo_program(mesh, axis: str, split: int, h: int, pshape, dtype_name: str):
    """Cached halo-exchange program: two ppermute ring shifts returning the
    (from_prev, from_next) halo slices per device; edge devices get zeros
    (the TPU rendering of reference dndarray.py:360-441)."""
    from jax.sharding import PartitionSpec

    p = mesh.devices.size
    block = pshape[split] // p

    def spec():
        ent = [None] * len(pshape)
        ent[split] = axis
        return PartitionSpec(*ent)

    def kernel(x):  # local shard: block along split
        lead = jax.lax.slice_in_dim(x, 0, h, axis=split)
        trail = jax.lax.slice_in_dim(x, block - h, block, axis=split)
        # device d+1 receives d's trailing slice; device d-1 receives d's
        # leading slice; unaddressed edges receive zeros
        from_prev = jax.lax.ppermute(trail, axis, [(j, j + 1) for j in range(p - 1)])
        from_next = jax.lax.ppermute(lead, axis, [(j, j - 1) for j in range(1, p)])
        return from_prev, from_next

    return jax.jit(
        jax.shard_map(
            kernel, mesh=mesh, in_specs=spec(), out_specs=(spec(), spec()), check_vma=False
        )
    )


@functools.lru_cache(maxsize=None)
def _halo_exchange_kernel(axis: str, split: int, h: int, block: int, p: int):
    """The halo exchange as an UNJITTED multi-output kernel for the deferred
    path: the same two ppermute ring shifts as :func:`_halo_program`, handed
    to ``fusion.defer_apply`` so the exchange compiles INTO the enclosing
    chain's program instead of dispatching on its own. Cached so repeated
    records keep one function identity (one program-cache key)."""

    def kernel(x):  # local shard: block along split
        lead = jax.lax.slice_in_dim(x, 0, h, axis=split)
        trail = jax.lax.slice_in_dim(x, block - h, block, axis=split)
        from_prev = jax.lax.ppermute(trail, axis, [(j, j + 1) for j in range(p - 1)])
        from_next = jax.lax.ppermute(lead, axis, [(j, j - 1) for j in range(1, p)])
        return from_prev, from_next

    kernel.__name__ = f"halo_exchange_s{split}_h{h}"
    return kernel


@functools.lru_cache(maxsize=None)
def _halo_concat_program(mesh, axis: str, split: int, pshape, hshape, dtype_name: str):
    """Cached per-device ``[from_prev | local | from_next]`` concatenation
    along the split axis (reference array_with_halos, dndarray.py:332-341)."""
    from jax.sharding import PartitionSpec

    def spec():
        ent = [None] * len(pshape)
        ent[split] = axis
        return PartitionSpec(*ent)

    def kernel(prev, x, nxt):
        return jnp.concatenate([prev, x, nxt], axis=split)

    return jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(spec(), spec(), spec()),
            out_specs=spec(),
            check_vma=False,
        )
    )


@functools.lru_cache(maxsize=None)
def _pad_program(widths: Tuple[Tuple[int, int], ...], target) -> callable:
    """Cached compiled pad-with-out-sharding program (keyed on pad widths and
    the target NamedSharding so repeated ragged wraps never retrace). The
    input is never donated here: a pad's output is strictly larger than its
    input, so XLA cannot reuse the buffer (donation would only warn)."""
    return jax.jit(lambda a: jnp.pad(jnp.asarray(a), widths), out_shardings=target)


@functools.lru_cache(maxsize=None)
def _donating_reshard_program(target) -> callable:
    """Cached jitted identity-with-out-sharding that DONATES its input buffer.

    Used by the in-place mutators (``resplit_`` of a previously-padded
    payload, ``__setitem__`` repads) whose source array is a freshly-created
    temporary no caller can hold: the reshard is same-shape, so XLA reuses
    the donated buffer instead of keeping source and destination alive."""
    return jax.jit(lambda a: a, out_shardings=target, donate_argnums=(0,))


def _reshard_donating(array: jax.Array, split: Optional[int], comm: MeshCommunication) -> jax.Array:
    """Place ``array`` under the ``split`` sharding, donating its buffer.
    Only for freshly-computed temporaries (see ``_donating_reshard_program``);
    tracers and ragged splits fall back to :func:`_ensure_split`."""
    if (
        isinstance(array, jax.core.Tracer)
        or array.ndim == 0
        or (split is not None and array.shape[split] % comm.size != 0)
    ):
        return _ensure_split(array, split, comm)
    return _donating_reshard_program(comm.sharding(array.ndim, split))(array)


def _pad_and_place(array: jax.Array, split: int, comm: MeshCommunication) -> jax.Array:
    """Physically realize a ragged split: zero-pad the split dim of the
    (logical) ``array`` to ``p * ceil(n/p)`` — a *suffix* of the global dim —
    and place the result under the split NamedSharding, so every device holds
    exactly one block-sized shard. One compiled pad-with-out-sharding program;
    no device ever materializes the full array at rest. The reference instead
    carries ragged per-rank chunks (reference dndarray.py:57-60); JAX rejects
    uneven NamedShardings outright, so pad+mask is the TPU rendering
    (SURVEY.md §7)."""
    n = int(array.shape[split])
    p = comm.size
    block = -(-n // p) if n else 0
    pad = block * p - n
    target = comm.sharding(array.ndim, split)
    if pad == 0:  # pragma: no cover - callers guard, kept for safety
        return jax.device_put(array, target)
    widths = [(0, 0)] * array.ndim
    widths[split] = (0, pad)
    return _pad_program(tuple(widths), target)(array)


def _ensure_split(array: jax.Array, split: Optional[int], comm: MeshCommunication) -> jax.Array:
    """Place ``array`` under the sharding implied by ``split`` if it is not
    already there. Eager resharding is one ``device_put`` (XLA collective).

    Dimensions not divisible by the mesh size cannot carry a NamedSharding in
    JAX (device_put/out_shardings/make_array_from_callback all reject them),
    so for a ragged ``split`` the array is returned untouched: the
    ``DNDarray`` constructor (every wrap site funnels through it) realizes
    the distribution physically via :func:`_pad_and_place`.
    """
    if array.ndim == 0:
        split = None
    if split is not None and array.shape[split] % comm.size != 0:
        return array  # ragged: the DNDarray constructor pads + places
    target = comm.sharding(array.ndim, split)
    current = getattr(array, "sharding", None)
    if current is not None:
        try:
            if current.is_equivalent_to(target, array.ndim):
                return array
        except (TypeError, ValueError, AttributeError):
            pass  # sharding types without a comparable form: place anew
    return jax.device_put(array, target)


jax.tree_util.register_pytree_node(
    DNDarray, DNDarray._tree_flatten, DNDarray._tree_unflatten
)
