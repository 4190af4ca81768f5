"""Memory-bounded exchange planner: staged redistribution schedules.

The flat exchange (:func:`dryad_tpu.ops.shuffle.exchange`) materializes
the full ``(P, B)`` send buffer per column and ships it in one
``all_to_all``, so peak extra HBM per device grows linearly with mesh
width ``P``.  Following "Memory-efficient array redistribution through
portable collective communication" (arxiv 2112.01075), any all-to-all
redistribution decomposes into a schedule of collective-permute *hops*:
hop ``(sd, sp)`` ships, from every device ``(d, p)``, the bucket
destined for device ``((d + sd) % D, (p + sp) % ici)``.  Each hop
touches one ``(B, ...)`` block per column, so grouping hops into rounds
of at most ``window`` bounds the in-flight exchange footprint at
``O(window * B)`` instead of ``O(P * B)``.

Topology ordering mirrors ``exec/combinetree.py``'s mesh model: the
ICI-local hops (``sd == 0``, traffic stays inside a slice) run first in
``window``-wide rounds; every DCN-crossing slice offset ``sd != 0``
then batches ALL of its intra-slice offsets into a single round, so a
2-slice hybrid mesh pays exactly one DCN round — the same root-hop
discipline PR 8's combine trees enforce.  (DCN rounds deliberately
ignore the window: minimizing the number of cross-slice launches beats
staging on the slow fabric, and hops within a round are still issued
one collective at a time.)

Everything here is static, pure-Python trace-time planning — no jax
imports, no data-dependent decisions — so a schedule is a compile-time
constant of the stage program and its byte accounting can be emitted as
``exchange_round`` events without any device readback.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple


@dataclasses.dataclass(frozen=True)
class ExchangeRound:
    """One scheduled round: a group of hops issued back to back.

    ``hops`` are ``(sd, sp)`` offset pairs — slice offset and
    intra-slice offset — never including the local ``(0, 0)`` hop,
    which ships zero network bytes and is placed where it stands.
    """

    index: int
    hops: Tuple[Tuple[int, int], ...]
    dcn: bool  # True when every hop in the round crosses slices

    @property
    def width(self) -> int:
        return len(self.hops)


@dataclasses.dataclass(frozen=True)
class ExchangeSchedule:
    """A full staged-exchange plan for one mesh shape.

    ``num_partitions == dcn_slices * ici_partitions`` always holds;
    on a single-slice (1-axis) mesh ``dcn_slices == 1`` and every hop
    is ICI-local.
    """

    num_partitions: int
    dcn_slices: int
    ici_partitions: int
    window: int
    rounds: Tuple[ExchangeRound, ...]

    @property
    def num_rounds(self) -> int:
        return len(self.rounds)

    @property
    def dcn_rounds(self) -> int:
        return sum(1 for r in self.rounds if r.dcn)

    @property
    def peak_width(self) -> int:
        """Most hops in-flight in any one round (peak-HBM multiplier)."""
        return max((r.width for r in self.rounds), default=0)

    def accounting(
        self, bucket_rows: int, row_bytes: int
    ) -> List[Dict[str, int]]:
        """Static per-round byte accounting for ``exchange_round`` events.

        ``bytes`` is the round's peak send-buffer footprint per device
        (``width * B * row_bytes``); ``ici_bytes``/``dcn_bytes`` split
        the shipped network bytes by fabric, mirroring
        ``combinetree.TreeShape.exchange_split`` semantics.
        """
        block = bucket_rows * row_bytes
        out = []
        for r in self.rounds:
            ici_hops = sum(1 for sd, _ in r.hops if sd == 0)
            dcn_hops = r.width - ici_hops
            out.append(
                {
                    "round": r.index,
                    "window": self.window,
                    "bytes": r.width * block,
                    "ici_bytes": ici_hops * block,
                    "dcn_bytes": dcn_hops * block,
                }
            )
        return out


def flat_accounting(
    num_partitions: int, dcn_slices: int, bucket_rows: int, row_bytes: int
) -> Dict[str, int]:
    """Byte accounting for the flat single-``all_to_all`` baseline.

    One pseudo-round with ``window=0``: the peak footprint is the whole
    ``(P, B)`` send buffer; network bytes exclude the self bucket.
    """
    ici = num_partitions // max(dcn_slices, 1)
    block = bucket_rows * row_bytes
    return {
        "round": 0,
        "window": 0,
        "bytes": num_partitions * block,
        "ici_bytes": (ici - 1) * block,
        "dcn_bytes": (dcn_slices - 1) * ici * block,
    }


def resolve_window(
    config_window: int,
    num_partitions: int,
    bucket_bytes: int,
    budget_bytes: int,
    hint: "int | None" = None,
    headroom_bytes: "int | None" = None,
) -> int:
    """The effective staged-exchange window for one compilation.

    The policy hook behind ``config.exchange_window``:

    - ``config_window >= 0`` — the static knob is an override; it is
      returned verbatim (0 = flat).
    - ``config_window == -1`` — auto, with precedence rewriter hint >
      measured headroom > configured budget.  An explicit ``hint``
      (the runtime rewriter's ``retune_exchange``) wins outright;
      otherwise the staging bound is ``headroom_bytes`` (live measured
      HBM headroom from ``obs.telemetry``) when available, else the
      configured ``budget_bytes`` — then pick flat while the whole
      ``P * bucket_bytes`` send buffer fits the bound, else the widest
      window whose ``O(window * B)`` staging footprint does (clamped
      to ``[1, P-1]``).

    Pure and deterministic: equal inputs always resolve equally, so
    the compile-cache key may include the resolved value without
    fragmenting the palette (callers quantize live headroom before
    passing it here for exactly that reason).
    """
    if config_window >= 0:
        return int(config_window)
    if hint is not None:
        return max(0, min(int(hint), max(num_partitions - 1, 0)))
    if num_partitions <= 1:
        return 0
    bound = (
        int(headroom_bytes) if headroom_bytes is not None
        else int(budget_bytes)
    )
    block = max(1, int(bucket_bytes))
    if num_partitions * block <= bound:
        return 0  # flat fits: one collective beats any staging
    return max(1, min(int(bound // block), num_partitions - 1))


def plan_exchange(
    num_partitions: int, window: int, dcn_slices: int = 1
) -> ExchangeSchedule:
    """Plan a staged exchange over a ``dcn_slices x ici`` mesh.

    ICI-local hops (intra-slice offsets ``1..ici-1``) are chunked into
    ``window``-wide rounds and scheduled first; each DCN slice offset
    ``1..D-1`` then gets exactly one round carrying all of its ``ici``
    intra-slice offsets (minimal cross-slice launches — one DCN round
    total on a 2-slice mesh).
    """
    if num_partitions < 1:
        raise ValueError(f"num_partitions must be >= 1: {num_partitions}")
    if window < 1:
        raise ValueError(f"window must be >= 1 for staged plans: {window}")
    if dcn_slices < 1 or num_partitions % dcn_slices:
        raise ValueError(
            f"dcn_slices {dcn_slices} must divide num_partitions "
            f"{num_partitions}"
        )
    ici = num_partitions // dcn_slices
    rounds: List[ExchangeRound] = []
    ici_hops = [(0, sp) for sp in range(1, ici)]
    for i in range(0, len(ici_hops), window):
        rounds.append(
            ExchangeRound(
                index=len(rounds),
                hops=tuple(ici_hops[i : i + window]),
                dcn=False,
            )
        )
    for sd in range(1, dcn_slices):
        rounds.append(
            ExchangeRound(
                index=len(rounds),
                hops=tuple((sd, sp) for sp in range(ici)),
                dcn=True,
            )
        )
    return ExchangeSchedule(
        num_partitions=num_partitions,
        dcn_slices=dcn_slices,
        ici_partitions=ici,
        window=window,
        rounds=tuple(rounds),
    )
