"""ICI-native device exchange tier: HBM→HBM bucketed-span movement for
intra-pod peers, with the wire-format host shuffle as the cross-pod DCN
tier and the fault-tolerant fallback.

The host exchange (``hostshuffle.py``) round-trips every block through
host RAM and the shared filesystem — the right data plane BETWEEN pods,
and the only one that survives a peer death, but a detour for chips
that share an ICI fabric.  This module adds the intra-pod tier:

* ``probe_topology`` — the replica-deterministic tier split: which
  process ids share an ICI domain.  Pure function of the conf override
  string, the live set, and replicated jax world facts; its fingerprint
  rides ``crossproc.decision_inputs`` into the decision-trace hash, so
  a process whose view of the tiers diverges aborts structured at the
  plan round instead of hanging a device collective.
* ``plan_side`` — per-exchange activation from AGREED inputs only (the
  gathered plan-round manifests' side totals vs ``ici.minBytes``):
  every replica derives the same use-the-device-tier verdict, because
  asymmetric participation in a collective is a hang, not an error.
* ``device_exchange`` — the data plane: per-receiver spans (the
  contiguous slices ``kernels.partition_bucket`` already emits) pack
  into fixed-capacity per-peer buffers, ONE all-to-all moves them over
  the interconnect, and the received blocks unpack per sender — run
  boundaries intact, so the range lane's presorted runs merge exactly
  as if they had crossed the host path.  The executable is built
  through ``stagecompile.StageCache`` (r11): the exchange fuses into a
  cached stage program instead of being a fresh-jit host seam.  The
  collective is ``lax.all_to_all`` under ``shard_map`` on every
  platform (XLA routes it over ICI on a TPU slice), so the multi-device
  CPU mesh exercises the identical pack/exchange/unpack program in
  tier-1.
* ``IciUnavailable`` — the device tier cannot serve this exchange (no
  spanning device world, too few devices, a shape the pack cannot
  express, an injected fault): the spans fold back onto the host tier,
  counted, never partial rows.  A lowering, compile or runtime error of
  the collective itself is NOT unavailability and propagates; a peer
  death mid-copy surfaces at the host barrier and takes the ordinary
  r12 recovery.

Control-plane rounds never move here: manifests, adaptive stats,
decision traces and recovery agreement stay on the host path, so the
device tier adds ZERO barriers to the exchange protocol.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..columnar import ColumnBatch, ColumnVector
from .. import wire

__all__ = ["IciUnavailable", "TierSplit", "probe_topology", "plan_side",
           "schema_eligible", "device_exchange", "local_device_exchange",
           "ICI_AXIS"]

#: mesh axis name for the device-exchange collective (distinct from the
#: intra-process compute mesh's DATA_AXIS: this axis spans EXCHANGE
#: peers, one device per participating process)
ICI_AXIS = "ici"


class IciUnavailable(RuntimeError):
    """Structured signal: the device tier cannot serve this exchange
    (no device world spanning the domain, too few devices, a pack shape
    it cannot express, an injected fault).  The caller folds the
    affected spans back into the host routed dict and rides the DCN
    tier — degradation, not an error.  A lowering, compile or runtime
    failure of the collective is never reported under this name."""


# ---------------------------------------------------------------------------
# tier split: which pids share an ICI domain (replica-deterministic)
# ---------------------------------------------------------------------------

class TierSplit:
    """The agreed partition of live process ids into ICI domains.

    ``domains`` is a tuple of sorted pid tuples covering every live pid
    exactly once; singleton domains are host-tier-only.  Constructed
    ONLY by ``probe_topology`` so every field is a pure function of
    replicated inputs."""

    __slots__ = ("pid", "domains", "_of")

    def __init__(self, pid: int, domains: Tuple[Tuple[int, ...], ...]):
        self.pid = int(pid)
        self.domains = domains
        self._of = {p: i for i, d in enumerate(domains) for p in d}

    def domain(self, pid: Optional[int] = None) -> Tuple[int, ...]:
        return self.domains[self._of[self.pid if pid is None else pid]]

    def same_domain(self, other: int) -> bool:
        mine = self._of.get(self.pid)
        return mine is not None and self._of.get(other) == mine

    def peers(self) -> List[int]:
        """My intra-domain exchange peers (self excluded), sorted."""
        return [p for p in self.domain() if p != self.pid]

    def fingerprint(self) -> List[str]:
        """Canonical component for the decision-trace hash: one
        'a,b,c' string per domain, in domain order (domains are built
        sorted, so equal splits hash equal on every replica)."""
        return [",".join(str(p) for p in d) for d in self.domains]


def _world_slice_domains(live: Sequence[int]) -> Tuple[Tuple[int, ...], ...]:
    """Group live pids by the TPU slice their jax process belongs to —
    replicated world facts in a real multi-controller deployment (every
    process sees the same global device list).  Anything that is not a
    multi-process accelerator world (CPU tests, single-host runs)
    yields singleton domains: the host tier, everywhere."""
    import jax
    try:
        if jax.process_count() < 2:
            return tuple((int(p),) for p in sorted(live))
        by_slice: Dict[int, List[int]] = {}
        for d in jax.devices():
            s = int(getattr(d, "slice_index", 0) or 0)
            by_slice.setdefault(s, []).append(int(d.process_index))
        live_set = frozenset(int(p) for p in live)
        domains: List[Tuple[int, ...]] = []
        seen: List[int] = []
        for s in sorted(by_slice):
            # pid == jax process index: the multi-controller SPMD
            # contract this engine already runs under
            members = sorted(set(by_slice[s]) & live_set)
            if members:
                domains.append(tuple(members))
                seen.extend(members)
        for p in sorted(live_set - frozenset(seen)):
            domains.append((p,))
        return tuple(sorted(domains))
    except Exception:
        return tuple((int(p),) for p in sorted(live))


def probe_topology(override: str, pid: int, n: int,
                   live: Sequence[int]) -> TierSplit:
    """The tier-split decision: partition the LIVE pids into ICI
    domains.  Replica-deterministic by construction — inputs are the
    conf override string, the process count, and the agreed live set
    (plus, on the auto path, replicated jax world facts); registered in
    ``analysis.determinism.DECISION_ROOTS`` so HZ109/HZ110 keep it free
    of nondeterministic sources.

    Override format: pipe-separated comma groups of pids ('0,1|2,3').
    Pids outside [0, n) or not live are dropped; a pid named twice
    keeps its first group; unmentioned live pids become singleton
    (host-tier-only) domains.  A malformed override falls back to
    singleton domains — misconfiguration must degrade, not abort."""
    live_sorted = sorted(int(p) for p in live)
    live_set = frozenset(live_sorted)
    if not override:
        return TierSplit(pid, _world_slice_domains(live_sorted))
    domains: List[Tuple[int, ...]] = []
    placed: List[int] = []
    try:
        for group in override.split("|"):
            members: List[int] = []
            for tok in group.split(","):
                tok = tok.strip()
                if not tok:
                    continue
                p = int(tok)
                if 0 <= p < n and p in live_set and p not in placed:
                    members.append(p)
                    placed.append(p)
            if members:
                domains.append(tuple(sorted(members)))
    except ValueError:
        domains, placed = [], []
    for p in live_sorted:
        if p not in placed:
            domains.append((p,))
    return TierSplit(pid, tuple(sorted(domains)))


# ---------------------------------------------------------------------------
# per-exchange activation (agreed inputs only)
# ---------------------------------------------------------------------------

class SidePlan:
    """One lane side's device-tier plan, derived from AGREED inputs:
    the tier split, the side's summed manifest bytes, and the max rows
    any single process observed (the pack capacity every participant
    must compile against).  ``active`` False means the side rides the
    host tier with no device attempt at all."""

    __slots__ = ("tier", "active", "cap_rows", "max_runs", "agreed_bytes")

    def __init__(self, tier: TierSplit, active: bool, cap_rows: int,
                 max_runs: int, agreed_bytes: int):
        self.tier = tier
        self.active = active
        self.cap_rows = cap_rows
        self.max_runs = max_runs
        self.agreed_bytes = agreed_bytes


def _pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def plan_side(tier: Optional[TierSplit], mans: Dict[int, dict], skey: str,
              min_bytes: int, max_runs: int = 1) -> Optional[SidePlan]:
    """Activate the device tier for one lane side from replica-shared
    inputs only: the gathered ``{xid}-plan`` manifests carry every
    process's observed per-side totals, so the byte gate and the pack
    capacity come out identical on every replica.  Local sizes never
    feed this decision — a locally-gated collective is a hang."""
    if tier is None or not tier.peers():
        return None
    total_bytes = 0
    max_rows = 0
    for s in sorted(mans):
        obs = (mans[s] or {}).get("sides", {}).get(skey)
        if obs:
            total_bytes += int(obs[0])
            max_rows = max(max_rows, int(obs[1]))
    active = total_bytes >= int(min_bytes) and max_rows > 0
    return SidePlan(tier, active, _pow2(max_rows), int(max_runs),
                    total_bytes)


def schema_eligible(batch: Optional[ColumnBatch]) -> bool:
    """Dictionary-coded columns are pinned to the host tier: code-space
    unification is host logic, and shipping codes without their word
    sidecar would be silent corruption.  Dictionary presence is a
    property of the column's source encoding (identical across replicas
    of one plan), so the verdict is replica-safe."""
    if batch is None:
        return False
    return all(v.dictionary is None for v in batch.vectors)


# ---------------------------------------------------------------------------
# pack / unpack: per-receiver spans <-> fixed-capacity per-peer buffers
# ---------------------------------------------------------------------------

def _wire_dtype(dtype) -> np.dtype:
    """The dtype a data plane crosses the device in.  A TPU emulates
    float64 (a value does not come back from the device bit for bit), and
    the device tier moves bits, not numbers: a float64 plane ships as its
    int64 view, taken and undone on the host.  Every other dtype ships as
    itself."""
    dtype = np.dtype(dtype)
    return np.dtype(np.int64) if dtype == np.float64 else dtype


def _pack_outbox(outbox: Dict[int, List[ColumnBatch]],
                 members: Sequence[int], template: ColumnBatch,
                 cap: int, max_runs: int):
    """Pack one participant's per-receiver batches into dense arrays:
    per column a ``(n_m, cap)`` data buffer (in its ``_wire_dtype``) and
    a ``(n_m, cap)`` mask, one ``(n_m, cap)`` row-validity plane, and a
    ``(n_m, max_runs)`` run-length table (run boundaries must survive the
    exchange — the range lane merges presorted runs, not concatenations).
    Peer slot order is the sorted domain member list, identical on every
    participant."""
    n_m = len(members)
    names = list(template.names)
    dtypes = [np.asarray(v.data).dtype for v in template.vectors]
    cols = [np.zeros((n_m, cap), dtype=_wire_dtype(dt)) for dt in dtypes]
    masks = [np.zeros((n_m, cap), dtype=bool) for _ in template.vectors]
    rowv = np.zeros((n_m, cap), dtype=bool)
    runlens = np.zeros((n_m, max_runs), dtype=np.int32)
    for slot, peer in enumerate(members):
        at = 0
        for run, b in enumerate(outbox.get(peer) or []):
            if run >= max_runs:
                raise IciUnavailable(
                    f"outbox run count exceeds the agreed pack shape "
                    f"({run + 1} > {max_runs})")
            rows = int(b.capacity)
            if at + rows > cap:
                raise IciUnavailable(
                    f"outbox rows exceed the agreed pack capacity "
                    f"({at + rows} > {cap})")
            for j, v in enumerate(b.vectors):
                cols[j][slot, at:at + rows] = np.ascontiguousarray(
                    np.asarray(v.data)[:rows], dtype=dtypes[j]
                ).view(cols[j].dtype)
                masks[j][slot, at:at + rows] = (
                    True if v.valid is None else np.asarray(v.valid)[:rows])
            rowv[slot, at:at + rows] = (
                True if b.row_valid is None
                else np.asarray(b.row_valid)[:rows])
            runlens[slot, run] = rows
            at += rows
    return names, cols, masks, rowv, runlens


def _unpack_inbox(names, template: ColumnBatch, cols, masks, rowv,
                  runlens, members: Sequence[int], self_pid: int
                  ) -> Dict[int, List[ColumnBatch]]:
    """Invert ``_pack_outbox`` on the received planes: slot ``s`` holds
    sender ``members[s]``'s rows for me, split back into its original
    run boundaries.  Senders with zero rows are omitted — the exact
    observable the host path produces when a sender publishes no part.
    All-true masks collapse back to None (the wire-semantics identity
    the rest of the engine already assumes)."""
    out: Dict[int, List[ColumnBatch]] = {}
    for slot, sender in enumerate(members):
        if sender == self_pid:
            continue
        runs = _slot_runs(template, names, cols, masks, rowv, runlens, slot)
        if runs:
            out[sender] = runs
    return out


def _slot_runs(template, names, cols, masks, rowv, runlens, slot
               ) -> List[ColumnBatch]:
    """One slot of the received planes, split back into its runs, each
    data plane viewed back from its wire dtype."""
    runs: List[ColumnBatch] = []
    at = 0
    for rows in (int(r) for r in np.asarray(runlens[slot]) if int(r) > 0):
        vectors = []
        for j, tv in enumerate(template.vectors):
            data = np.ascontiguousarray(cols[j][slot, at:at + rows]).view(
                np.asarray(tv.data).dtype)
            mask = np.asarray(masks[j][slot, at:at + rows])
            vectors.append(ColumnVector(
                data, tv.dtype, None if bool(mask.all()) else mask, None))
        rv = np.asarray(rowv[slot, at:at + rows])
        runs.append(ColumnBatch(list(names), vectors,
                                None if bool(rv.all()) else rv, rows))
        at += rows
    return runs


# ---------------------------------------------------------------------------
# the collective: one all-to-all over the exchange axis
# ---------------------------------------------------------------------------

def _a2a_step(*planes):
    """The per-device body: all-to-all every packed plane over
    ``ICI_AXIS``.  Each local view is ``(n_m, ...)`` — row d outbound
    to peer slot d — and comes back as row s inbound from peer slot s
    (``collective.hash_exchange``'s tiled split/concat idiom)."""
    from jax import lax
    return tuple(lax.all_to_all(x, ICI_AXIS, split_axis=0, concat_axis=0,
                                tiled=True) for x in planes)


def _exchange_stage(mesh, n_m: int, shapes, session=None):
    """The stage-executable for one exchange shape, built through the
    process ``StageCache`` (r11): the collective fuses into ONE cached
    jitted program per (mesh, pack shape) instead of a fresh-jit seam
    per exchange.  ``shapes`` is the canonical (dtype, shape) signature
    of every packed plane."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec
    from ..sql.stagecompile import stage_cache

    key = (f"ici-a2a:{n_m}:"
           + ":".join(f"{dt}{tuple(sh)}" for dt, sh in shapes)
           + ":" + ",".join(str(d.id) for d in mesh.devices.flat))

    def make():
        spec = PartitionSpec(ICI_AXIS)
        fn = shard_map(_a2a_step, mesh=mesh, in_specs=spec, out_specs=spec,
                       check_vma=False)
        return fn, None

    cache = stage_cache(session)
    entry = cache.get_or_build(key, make, n_ops=1, session=session)
    sharding = jax.sharding.NamedSharding(mesh, PartitionSpec(ICI_AXIS))
    return cache, entry, sharding


def _plane_shapes(cols, masks, rowv, runlens):
    planes = list(cols) + list(masks) + [rowv, runlens]
    return planes, [(str(p.dtype), p.shape) for p in planes]


def local_device_exchange(outboxes: Sequence[Dict[int, List[ColumnBatch]]],
                          template: ColumnBatch, max_runs: int = 1,
                          cap: Optional[int] = None, session=None
                          ) -> List[Dict[int, List[ColumnBatch]]]:
    """The device data plane on a LOCAL multi-device mesh: participant
    i's outbox rides device i, one all-to-all moves every span, and
    each participant's inbox unpacks per sender.  This is the tier-1
    face of ``device_exchange`` — same pack, same traceable, same
    unpack — run with ``--xla_force_host_platform_device_count`` on CPU
    (and on real chips by ``chip_smoke.py --chips 4``), so the
    cross-process path is a device specialization of tested logic.  Raises ``IciUnavailable``
    when the local world has too few devices."""
    import jax
    from .mesh import Mesh

    n_m = len(outboxes)
    devs = jax.local_devices()
    if n_m < 2 or len(devs) < n_m:
        raise IciUnavailable(
            f"local device world has {len(devs)} device(s); "
            f"{n_m} participants need one each")
    members = list(range(n_m))
    if cap is None:
        cap = _pow2(max(
            (sum(int(b.capacity) for b in bs)
             for ob in outboxes for bs in ob.values()), default=1))
    packs = [_pack_outbox(ob, members, template, cap, max_runs)
             for ob in outboxes]
    names = packs[0][0]
    # stack participants along axis 0: device i's shard is its pack
    stacked = []
    for j in range(len(packs[0][1]) * 2 + 2):
        def plane(p, j=j):
            _n, cols, masks, rowv, runlens = p
            flat = list(cols) + list(masks) + [rowv, runlens]
            return flat[j]
        stacked.append(np.concatenate([plane(p) for p in packs], axis=0))
    _, shapes = _plane_shapes(
        *(lambda p: (p[1], p[2], p[3], p[4]))(packs[0]))
    mesh = Mesh(np.asarray(devs[:n_m]), (ICI_AXIS,))
    cache, entry, sharding = _exchange_stage(mesh, n_m, shapes, session)
    placed = [jax.device_put(x, sharding) for x in stacked]
    received = cache.dispatch(entry, *placed)
    n_cols = len(packs[0][1])
    out: List[Dict[int, List[ColumnBatch]]] = []
    for i in range(n_m):
        sl = slice(i * n_m, (i + 1) * n_m)
        cols = [np.asarray(received[j])[sl] for j in range(n_cols)]
        masks = [np.asarray(received[n_cols + j])[sl]
                 for j in range(n_cols)]
        rowv = np.asarray(received[2 * n_cols])[sl]
        runlens = np.asarray(received[2 * n_cols + 1])[sl]
        inbox = _unpack_inbox(names, template, cols, masks, rowv,
                              runlens, members, self_pid=i)
        # the local harness keeps the self slot too: parity checks want
        # the full routed view back (the real path's own share never
        # leaves the process, so device_exchange drops it)
        inbox[i] = _slot_runs(template, names, cols, masks, rowv,
                              runlens, i)
        out.append(inbox)
    return out


def _fault_point(svc, exchange: str, point: str) -> None:
    """Fault-injection seam (``faults.FaultInjector.attach`` installs
    ``svc._ici_fault``): 'attempt' fires before any device work,
    'copy' fires at the moment the DMA would start."""
    hook = getattr(svc, "_ici_fault", None)
    if hook is not None:
        hook(exchange, point)


def device_exchange(svc, session, plan: SidePlan, exchange: str,
                    outbound: Dict[int, List[ColumnBatch]],
                    template: ColumnBatch) -> Dict[int, List[ColumnBatch]]:
    """Ship this process's intra-domain spans HBM→HBM and return the
    spans its domain peers shipped back, keyed by sender pid.

    The collective requires every domain member's symmetric
    participation — callers must gate ONLY on the replica-agreed
    ``plan`` — so the unavailability checks here are deterministic
    functions of world state every member shares: a world that cannot
    span the domain raises ``IciUnavailable`` identically everywhere
    (the CPU test reality: jax CPU backends run one process, so 2-real-
    process runs exercise exactly this structured fallback).  Data
    moved here never touches the exchange directory or the manifest
    protocol; the caller still runs the host exchange for the commit
    barrier and any cross-domain spans."""
    import jax

    _fault_point(svc, exchange, "attempt")
    members = sorted(plan.tier.domain())
    n_m = len(members)
    try:
        pack = _pack_outbox(outbound, members, template, plan.cap_rows,
                            plan.max_runs)
    except IciUnavailable:
        raise
    except Exception as e:
        # a shape the pack cannot express is a property of the plan's
        # schema (same on every replica): degrade structured
        raise IciUnavailable(
            f"pack failed for {exchange}: {str(e)[:200]}") from e
    moved = sum(wire.raw_nbytes(bs) for bs in outbound.values())
    _fault_point(svc, exchange, "copy")
    if jax.process_count() < 2:
        raise IciUnavailable(
            "single-process device world cannot span an ICI domain of "
            f"{n_m} processes; exchange {exchange} takes the host tier")
    # one device per domain member, led by each member's first device
    # (pid == jax process index: the multi-controller SPMD contract)
    by_proc: Dict[int, list] = {}
    for d in jax.devices():
        by_proc.setdefault(int(d.process_index), []).append(d)
    try:
        devs = [sorted(by_proc[m], key=lambda d: d.id)[0] for m in members]
    except KeyError as e:
        raise IciUnavailable(
            f"no devices for domain member {e}; exchange {exchange} "
            "takes the host tier")
    from .mesh import Mesh
    mesh = Mesh(np.asarray(devs), (ICI_AXIS,))
    _names, cols, masks, rowv, runlens = pack
    planes, shapes = _plane_shapes(cols, masks, rowv, runlens)
    # "unavailable" ended above (no spanning world, too few devices): a
    # lowering, compile or runtime error of the collective propagates
    cache, entry, sharding = _exchange_stage(mesh, n_m, shapes, session)
    placed = [jax.make_array_from_process_local_data(sharding, p)
              for p in planes]
    received = cache.dispatch(entry, *placed)
    n_cols = len(cols)
    got = [np.asarray(r.addressable_shards[0].data) for r in received]
    inbox = _unpack_inbox(_names, template, got[:n_cols],
                          got[n_cols:2 * n_cols], got[2 * n_cols],
                          got[2 * n_cols + 1], members,
                          self_pid=svc.pid)
    with svc._lock:
        svc.counters["ici_exchanges"] += 1
        svc.counters["ici_bytes_moved"] += int(moved)
    return inbox
