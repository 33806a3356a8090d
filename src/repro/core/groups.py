"""Pure (numpy/python) rank-group machinery shared by every comm backend.

This module is deliberately free of JAX so that its invariants can be
property-tested with hypothesis directly: communicator splits, ring
permutations, chunking/padding and the byte-cost model of each collective
algorithm are all plain functions of python ints.

Terminology
-----------
- *axis rank*: a device's index along the mesh axis a communicator spans.
- *comm rank*: the rank the user sees inside a (possibly split)
  communicator -- its position within its group.
- *groups*: a partition of the axis ranks into equally-sized tuples.
  ``groups=None`` means the single group ``(0, 1, ..., P-1)``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import pickle
from typing import Any, Callable, Sequence

Groups = tuple[tuple[int, ...], ...]


def world_groups(size: int) -> Groups:
    return (tuple(range(size)),)


def validate_groups(groups: Groups, size: int) -> None:
    """Groups must partition range(size) into equal-size, duplicate-free sets."""
    flat = [r for g in groups for r in g]
    if sorted(flat) != list(range(size)):
        raise ValueError(
            f"groups {groups} do not partition range({size})")
    sizes = {len(g) for g in groups}
    if len(sizes) != 1:
        raise ValueError(
            f"unequal group sizes {sorted(len(g) for g in groups)}; the SPMD "
            "backends require uniform sub-communicator sizes")


def split_groups(parent: Groups, colors: Sequence[int],
                 keys: Sequence[int]) -> dict[int, Groups]:
    """MPI_Comm_split semantics (paper section 3.1).

    ``colors[i]``/``keys[i]`` are given per *comm rank* ``i`` of each parent
    group (every parent group is split with the same color/key tables, which
    is what a mesh-structured split needs). Within a color, members are
    ordered by (key, parent comm rank) -- exactly the sort the MPIgnite root
    performs before broadcasting the new rank mapping.

    Returns ``{color: groups}`` where each value partitions only the ranks
    holding that color (across all parent groups).
    """
    n = len(colors)
    if len(keys) != n:
        raise ValueError("colors and keys must have equal length")
    for g in parent:
        if len(g) != n:
            raise ValueError(
                f"color/key tables (len {n}) must match parent group size {len(g)}")
    out: dict[int, list[tuple[int, ...]]] = {}
    for g in parent:
        bycolor: dict[int, list[tuple[int, int]]] = {}
        for comm_rank, axis_rank in enumerate(g):
            bycolor.setdefault(colors[comm_rank], []).append(
                (keys[comm_rank], comm_rank))
        for color, members in bycolor.items():
            members.sort()  # by (key, parent comm rank)
            out.setdefault(color, []).append(
                tuple(g[comm_rank] for _, comm_rank in members))
    return {c: tuple(gs) for c, gs in out.items()}


def context_id(groups: Groups, parent_ctx: int) -> int:
    """Deterministic context identifier for a communicator (paper: used to
    fence messages within the group that participated in a split)."""
    h = hashlib.sha256(repr((parent_ctx, groups)).encode()).hexdigest()
    return int(h[:12], 16)


def comm_rank_table(groups: Groups, size: int) -> list[int]:
    """axis rank -> comm rank (position within its group)."""
    table = [-1] * size
    for g in groups:
        for i, axis_rank in enumerate(g):
            table[axis_rank] = i
    return table


def group_id_table(groups: Groups, size: int) -> list[int]:
    """axis rank -> index of the group containing it."""
    table = [-1] * size
    for gi, g in enumerate(groups):
        for axis_rank in g:
            table[axis_rank] = gi
    return table


def ring_perm(groups: Groups, shift: int) -> list[tuple[int, int]]:
    """Global (src, dst) pairs realizing a ring shift by ``shift`` within
    every group simultaneously. A union of in-group cycles is still a valid
    global permutation, which is what lax.ppermute requires."""
    pairs: list[tuple[int, int]] = []
    for g in groups:
        p = len(g)
        for i, src in enumerate(g):
            pairs.append((src, g[(i + shift) % p]))
    return pairs


def p2p_perm(groups: Groups, pairs: Sequence[tuple[int, int]],
             size: int) -> list[tuple[int, int]]:
    """Translate comm-rank (src, dst) pairs into global axis-rank pairs,
    enforcing the paper's context isolation *statically*: a pair that crosses
    group boundaries is a trace-time error, and duplicate senders/receivers
    (not a permutation) are rejected."""
    gid = group_id_table(groups, size)
    out: list[tuple[int, int]] = []
    seen_src: set[int] = set()
    seen_dst: set[int] = set()
    for src_cr, dst_cr in pairs:
        for g in groups:
            p = len(g)
            if not (0 <= src_cr < p and 0 <= dst_cr < p):
                raise ValueError(
                    f"p2p rank pair ({src_cr},{dst_cr}) out of range for "
                    f"communicator of size {p}")
            s, d = g[src_cr], g[dst_cr]
            if gid[s] != gid[d]:  # cannot happen given construction; guard anyway
                raise ValueError(
                    "message would cross sub-communicator boundary "
                    f"({s} -> {d}); context isolation violated")
            if s in seen_src:
                raise ValueError(f"duplicate sender comm-rank {src_cr}")
            if d in seen_dst:
                raise ValueError(f"duplicate receiver comm-rank {dst_cr}")
            seen_src.add(s)
            seen_dst.add(d)
            out.append((s, d))
    return out


# ---------------------------------------------------------------------------
# Byte-cost model (per device, per call) for each collective algorithm.
# The message runtime's measured-vs-analytic cross-check (obs/metrics.py)
# compares against these counts; the SPMD cost log (comm.py) records its
# entries as ``CollectiveCost``.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CollectiveCost:
    op: str
    backend: str
    bytes_per_device: int
    steps: int
    #: logged from inside a nonblocking (i*) collective: these bytes are
    #: candidates for communication/compute overlap, not serial time.
    overlap: bool = False


def collective_cost(op: str, backend: str, nbytes: int, p: int) -> CollectiveCost:
    """Bytes sent per device for one collective of payload ``nbytes`` over a
    group of size ``p``.

    linear -- the paper's phase-1 master-relay: gather-to-root then
    root-broadcast, O(p * S) wire bytes, 2(p-1) serial full-size steps.
    ring   -- phase-2 peer-to-peer: chunked reduce-scatter + all-gather,
    O(2S) bytes in 2(p-1) chunk-size steps.
    segmented -- the message-runtime segmented ring (reduce-scatter +
    all-gather over MPIGNITE_SEGMENT_BYTES pieces): same bandwidth-optimal
    byte count as ``ring``, pipelined into segment-size steps.
    native -- XLA collectives; modeled with the ring byte count (XLA lowers
    to ring/tree variants with the same asymptotics) but fusable/overlappable.
    """
    if p <= 1:
        return CollectiveCost(op, backend, 0, 0)
    S = nbytes
    if backend == "linear":
        table = {
            "allreduce": (2 * (p - 1) * S, 2 * (p - 1)),
            "broadcast": ((p - 1) * S, p - 1),
            "allgather": (2 * (p - 1) * S, 2 * (p - 1)),   # relay in + relay out
            "reducescatter": ((2 * p - 1) * S // 1, 2 * (p - 1)),
            "alltoall": ((p - 1) * S, p - 1),              # relay full buffer
            "p2p": (S, 1),
        }
    elif backend in ("ring", "native", "segmented"):
        table = {
            "allreduce": (2 * S * (p - 1) // p, 2 * (p - 1)),
            # segmented maps to the ring relay in SPMD (comm._algo), so
            # its broadcast moves ring's bytes, not native's fused S
            "broadcast": ((p - 1) * S if backend != "native" else S, p - 1),
            "allgather": (S * (p - 1) // p, p - 1),
            "reducescatter": (S * (p - 1) // p, p - 1),
            "alltoall": (S * (p - 1) // p, p - 1),
            "p2p": (S, 1),
        }
    else:
        raise ValueError(f"unknown backend {backend}")
    b, steps = table[op]
    return CollectiveCost(op, backend, int(b), steps)


#: Transport-tier rows for the analytic time estimate: nominal per-hop
#: latency and bandwidth for each data-plane channel the cluster runtime
#: can pick. These are planning figures (same spirit as the byte model
#: above), not measurements -- the shm benchmark gate compares *measured*
#: ratios and only uses these to annotate the expected direction.
#: ``relay`` is the driver-bounce fallback: two TCP hops per message.
TRANSPORT_COST = {
    "tcp": {"latency_us": 50.0, "gib_s": 3.0},
    "shm": {"latency_us": 5.0, "gib_s": 12.0},
    "relay": {"latency_us": 100.0, "gib_s": 1.5},
}


def transport_time_us(transport: str, nbytes: int, steps: int = 1) -> float:
    """Analytic wall-time estimate for moving ``nbytes`` over ``steps``
    serial hops of one transport tier (alpha-beta model over the
    ``TRANSPORT_COST`` rows)."""
    row = TRANSPORT_COST[transport]
    return steps * row["latency_us"] + \
        (nbytes / (row["gib_s"] * 2 ** 30)) * 1e6


def pad_to_multiple(n: int, p: int) -> int:
    return (n + p - 1) // p * p


# ---------------------------------------------------------------------------
# Segmented-ring chunk/segment math. Pure ints so every rank computes the
# identical partition from (payload size, world size, segment size) alone --
# no negotiation messages -- and so the invariants are hypothesis-testable.
# ---------------------------------------------------------------------------

def chunk_bounds(n: int, p: int) -> list[int]:
    """``p + 1`` boundaries splitting ``range(n)`` into ``p`` contiguous
    near-equal chunks (the first ``n % p`` chunks get one extra element,
    so no payload size needs padding). Chunk ``i`` is
    ``[bounds[i], bounds[i+1])``; chunks may be empty when ``n < p``."""
    if p < 1:
        raise ValueError(f"need at least one chunk, got p={p}")
    base, rem = divmod(n, p)
    bounds = [0]
    for i in range(p):
        bounds.append(bounds[-1] + base + (1 if i < rem else 0))
    return bounds


def segment_spans(length: int, seg: int) -> list[tuple[int, int]]:
    """``(start, stop)`` spans of at most ``seg`` elements covering
    ``range(length)`` in order -- the per-hop message schedule of a
    segmented transfer. Empty for ``length <= 0`` (an empty chunk moves
    zero messages, on both ends, by construction)."""
    if seg < 1:
        raise ValueError(f"segment size must be >= 1, got {seg}")
    if length <= 0:
        return []
    return [(a, min(a + seg, length)) for a in range(0, length, seg)]


# ---------------------------------------------------------------------------
# Elastic-world remap math. Membership changes (shrink-to-survivors,
# grow-on-join) re-number the world; every schedule above is a pure
# function of (rank, size), so remapping is nothing but a rank table.
# ---------------------------------------------------------------------------

def buddy_rank(rank: int, size: int, offset: int = 1) -> int:
    """The rank holding this rank's buddy snapshot: the next rank around
    the ring (``offset`` hops). A world of one is its own buddy."""
    if size < 1:
        raise ValueError(f"need at least one rank, got size={size}")
    return (rank + offset) % size


def survivor_map(world: Sequence[int], dead: Sequence[int]) -> dict[int, int]:
    """Contiguous re-numbering of the survivors of ``world`` (stable
    identities, e.g. launch slots) after ``dead`` members are removed:
    ``{member: new_rank}`` preserving the original order. Raises if
    nothing survives."""
    dead_set = set(dead)
    survivors = [m for m in world if m not in dead_set]
    if not survivors:
        raise ValueError(f"no survivors in world {list(world)} "
                         f"after deaths {sorted(dead_set)}")
    return {m: i for i, m in enumerate(survivors)}


def remap_group(group: Sequence[int], rank_map: dict[int, int]
                ) -> tuple[int, ...]:
    """Translate a group of old ranks through a membership remap,
    dropping members that did not survive. Order (and therefore every
    ring schedule derived from the group) is preserved."""
    return tuple(rank_map[r] for r in group if r in rank_map)


# ---------------------------------------------------------------------------
# Dataset partition placement (``repro.data.dataset``). Placement is a pure
# function of (partition, world size) -- every rank and the driver compute
# the identical owner table with zero negotiation messages -- and it is
# membership-aware by construction: after a shrink-to-survivors the same
# formula over the new size re-homes the dead ranks' partitions onto
# survivors, which is exactly what lineage recovery needs.
# ---------------------------------------------------------------------------

def partition_owner(part: int, nparts: int, size: int) -> int:
    """World rank owning dataset partition ``part`` (round-robin, so a
    shrink moves the fewest partitions and keeps load balanced)."""
    if not 0 <= part < nparts:
        raise ValueError(f"partition {part} out of range({nparts})")
    if size < 1:
        raise ValueError(f"need at least one rank, got size={size}")
    return part % size


def owned_partitions(rank: int, nparts: int, size: int) -> list[int]:
    """Partitions ``rank`` owns under round-robin placement, ascending.
    Empty when ``nparts < size`` leaves this rank without work (it still
    participates in every shuffle collective with empty contributions)."""
    if not 0 <= rank < size:
        raise ValueError(f"rank {rank} out of range({size})")
    return list(range(rank, nparts, size))


def shuffle_rounds(nparts: int, size: int) -> int:
    """Number of shuffle rounds every rank posts per wide stage. The
    collectives are matched by call order, so the count must be uniform:
    ranks owning fewer than ``shuffle_rounds`` partitions contribute
    empty chunks in their trailing rounds."""
    if size < 1:
        raise ValueError(f"need at least one rank, got size={size}")
    return -(-nparts // size)


def lost_partitions(nparts: int, dead_old_ranks: Sequence[int],
                    old_size: int) -> set[int]:
    """Partitions whose materialized copy died with their previous-epoch
    owner -- the set a post-shrink retry must recompute from lineage
    (``shrink_info['dead_old_ranks']`` / ``['old_size']`` feed this)."""
    dead = set(dead_old_ranks)
    return {p for p in range(nparts)
            if partition_owner(p, nparts, old_size) in dead}


def stable_key_hash(key: Any) -> int:
    """Process-stable shuffle hash of an arbitrary picklable key.

    Python's builtin ``hash`` is salted per process (PYTHONHASHSEED), so
    two executors would route the same key to different partitions;
    blake2b over the pickle of the key gives every process -- and the
    single-process oracle -- the identical bucket. Keys must pickle
    deterministically (strings, ints, tuples of those all do)."""
    blob = pickle.dumps(key, protocol=4)
    return int.from_bytes(hashlib.blake2b(blob, digest_size=8).digest(),
                          "big")


ReduceFn = Callable  # (a, b) -> elementwise combine; must be associative
