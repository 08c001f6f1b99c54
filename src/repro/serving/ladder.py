"""Multi-tier compiled-plan ladder: one plan, several band budgets.

A *tier* is the compiled schedule of the serving plan with every layer's
band assignment capped at a budget (``min(layer_bands, cap)``).  The key
property making a ladder cheap to hold and exact to reason about: band
truncation of an exploded operator **is a prefix slice** —
``explosion_basis`` builds the truncated basis as ``full[..., :b, :b]``,
so

    explode(kernel, bands=b) == explode(kernel, bands=64)[..., :b, :, :b]

bit-exactly (same contractions, elementwise prefix), and the folded
batch-norm scale/shift commute with the slice.  Tiers therefore *derive*
from the top tier's operators by slicing — no re-explosion, no second
copy of the weights at build time — and compile through the ordinary
``core.plan.compile_plan`` into tile-packed schedules.  Tiers whose
capped band assignment collapses onto an earlier tier's share that tier's
``CompiledPlan`` object outright.

Serialization rides on the plan artifact: :func:`save_ladder` stores only
the base plan (``core.plan.save_plan``) plus a small ladder manifest
through ``CheckpointManager``; :func:`load_ladder` restores the plan and
re-derives the tiers, which is bit-exact because the derivation is a
deterministic slice + pack.  The manifest records each tier's band
assignment so a stale ladder (saved against a different plan) is rejected
loudly instead of silently serving different math.
"""
from __future__ import annotations

import os
from typing import Any, NamedTuple

from repro.core import dct as dctlib
from repro.core import plan as planlib

__all__ = [
    "DEFAULT_CAPS",
    "PlanTier",
    "PlanLadder",
    "cap_operator",
    "cap_plan",
    "build_ladder",
    "save_ladder",
    "load_ladder",
]

#: default band budgets, best quality first.  ``None`` = the plan's own
#: (autotuned) assignment, untouched; ints cap every layer at that budget.
DEFAULT_CAPS = (None, 48, 32, 24)

_LADDER_SUBDIR = "ladder"
_LADDER_FORMAT = 1


class PlanTier(NamedTuple):
    """One rung: the capped plan and its compiled schedule.

    ``shared_with`` is the index of the earlier tier whose ``CompiledPlan``
    this tier reuses (its cap changed nothing), else ``None``.
    """

    name: str
    cap: int | None
    bands: dict[str, int]
    plan: planlib.InferencePlan
    compiled: planlib.CompiledPlan
    shared_with: int | None = None


class PlanLadder(NamedTuple):
    """An ordered tier stack, index 0 = best quality (widest bands).

    ``buckets`` (optional, trailing for positional-construction compat)
    records the batch capture buckets the ladder was prepared to serve —
    ``serving.grid.PlanGrid`` defaults to them, and the manifest
    persists them so a serve process restores the same grid extent it
    warmed up last time.  ``None`` = derive the aphrodite schedule from
    the scheduler's batch size at grid-build time.
    """

    tiers: tuple[PlanTier, ...]
    base: planlib.InferencePlan
    caps: tuple[int | None, ...]
    image_size: int | None
    vmem_budget: int
    buckets: tuple[int, ...] | None = None

    @property
    def top(self) -> PlanTier:
        return self.tiers[0]

    def __len__(self) -> int:
        return len(self.tiers)


def _tier_name(cap: int | None) -> str:
    return "top" if cap is None else f"b{cap}"


def cap_operator(op, cap: int):
    """Cap one ``ConvOperator`` at ``cap`` bands by prefix-slicing its Ξ.

    Bit-exact vs re-exploding at the capped band count (the basis
    truncation *is* this slice); factored operators (no materialised Ξ)
    and pointwise ones (a channel matrix with no band axis) just lower
    their ``bands`` field — their apply path slices the coefficients to
    it at run time.
    """
    b = min(op.bands, cap)
    if b == op.bands:
        return op
    xi = op.xi
    if xi is not None and op.path != "pointwise":
        xi = xi[:, :, :, :b, :, :b]
    return op._replace(xi=xi, bands=b)


def cap_plan(plan: planlib.InferencePlan, cap: int | None
             ) -> planlib.InferencePlan:
    """Derive the plan at band budget ``cap`` (``None`` → the plan itself).

    Shares every operator the cap does not touch; touched operators are
    prefix-slices of the originals (see :func:`cap_operator`).
    """
    if cap is None or cap >= max(plan.bands.values()):
        return plan
    if not 8 <= cap <= dctlib.NFREQ or cap % 8:
        raise ValueError(
            f"tier cap must be a multiple of 8 in [8, {dctlib.NFREQ}], "
            f"got {cap}")
    operators: dict[str, Any] = {}
    for name, entry in plan.operators.items():
        if isinstance(entry, dict):
            operators[name] = {slot: cap_operator(op, cap)
                               for slot, op in entry.items()}
        else:
            operators[name] = cap_operator(entry, cap)
    bands = {k: min(v, cap) for k, v in plan.bands.items()}
    provenance = dict(plan.provenance or {}, tier_cap=cap)
    return plan._replace(operators=operators, bands=bands,
                         provenance=provenance)


def _validate_caps(caps) -> tuple[int | None, ...]:
    caps = tuple(caps)
    if not caps:
        raise ValueError("ladder needs at least one tier")
    if caps[0] is not None and any(c is None for c in caps):
        raise ValueError("the uncapped (None) tier must come first")
    numeric = [c for c in caps if c is not None]
    if numeric != sorted(numeric, reverse=True) or len(set(caps)) != len(caps):
        raise ValueError(
            f"tier caps must be strictly decreasing (best first): {caps}")
    return caps


def build_ladder(plan: planlib.InferencePlan, *,
                 caps=DEFAULT_CAPS,
                 image_size: int | None = None,
                 vmem_budget: int = planlib.VMEM_BUDGET,
                 buckets=None) -> PlanLadder:
    """Compile ``plan`` into a tier ladder at the given band budgets.

    Tiers are ordered best-quality first; caps wider than the plan's own
    assignment collapse onto the previous tier (sharing its compiled
    schedule rather than compiling a duplicate).  ``buckets`` pins the
    batch capture buckets the serving grid should precompile (see
    :class:`PlanLadder`).
    """
    caps = _validate_caps(caps)
    if buckets is not None:
        from repro.serving.grid import validate_buckets

        buckets = validate_buckets(buckets)
    tiers: list[PlanTier] = []
    by_bands: dict[tuple, int] = {}
    for cap in caps:
        capped = cap_plan(plan, cap)
        key = tuple(sorted(capped.bands.items()))
        shared = by_bands.get(key)
        if shared is not None:
            prev = tiers[shared]
            tiers.append(PlanTier(_tier_name(cap), cap, dict(capped.bands),
                                  prev.plan, prev.compiled, shared))
            continue
        compiled = planlib.compile_plan(capped, vmem_budget=vmem_budget,
                                        image_size=image_size)
        by_bands[key] = len(tiers)
        tiers.append(PlanTier(_tier_name(cap), cap, dict(capped.bands),
                              capped, compiled))
    return PlanLadder(tuple(tiers), plan, caps, image_size, vmem_budget,
                      buckets)


# --------------------------------------------------------------------------
# Serialization: base plan + manifest; tiers re-derive bit-exactly
# --------------------------------------------------------------------------


def save_ladder(ladder: PlanLadder, directory: str, *,
                save_base: bool = True) -> None:
    """Persist a ladder under ``directory``.

    ``directory`` is a plan directory (``core.plan.save_plan`` layout);
    the ladder manifest goes into ``directory/ladder`` through the
    checksummed ``CheckpointManager`` store.  ``save_base=False`` skips
    re-saving the base plan when the caller already did (the serve path:
    ``prepare_plan`` saved it before the ladder was built).
    """
    from repro.checkpoint import CheckpointManager

    if save_base:
        planlib.save_plan(ladder.base, directory)
    extra = {
        "kind": "jpeg_plan_ladder",
        "format": _LADDER_FORMAT,
        "caps": [c for c in ladder.caps],
        "image_size": ladder.image_size,
        "vmem_budget": int(ladder.vmem_budget),
        # absent in pre-grid manifests; .get(None) on load keeps format 1
        "buckets": (None if ladder.buckets is None
                    else [int(b) for b in ladder.buckets]),
        "tiers": [{"name": t.name, "cap": t.cap, "bands": t.bands,
                   "shared_with": t.shared_with} for t in ladder.tiers],
    }
    CheckpointManager(os.path.join(directory, _LADDER_SUBDIR)).save(
        0, {}, extra=extra)


def load_ladder(directory: str, *,
                plan: planlib.InferencePlan | None = None) -> PlanLadder:
    """Restore a ladder saved by :func:`save_ladder`.

    The base plan restores bit-exactly through the checkpoint store and
    the tiers re-derive from it (deterministic slice + pack ⇒ bit-exact
    tier schedules).  A manifest whose recorded per-tier band assignments
    disagree with the restored plan — a ladder saved against a *different*
    plan — is rejected with ``ValueError``.
    """
    from repro.checkpoint import CheckpointManager

    _, _, extra = CheckpointManager(
        os.path.join(directory, _LADDER_SUBDIR)).restore_tree()
    if extra.get("kind") != "jpeg_plan_ladder":
        raise ValueError(f"{directory} does not hold a plan ladder")
    if extra.get("format") != _LADDER_FORMAT:
        raise ValueError(
            f"unsupported ladder format {extra.get('format')!r}")
    if plan is None:
        plan = planlib.load_plan(directory)
    caps = tuple(None if c is None else int(c) for c in extra["caps"])
    buckets = extra.get("buckets")
    ladder = build_ladder(
        plan, caps=caps,
        image_size=(None if extra.get("image_size") is None
                    else int(extra["image_size"])),
        vmem_budget=int(extra["vmem_budget"]),
        buckets=None if buckets is None else tuple(int(b) for b in buckets))
    for tier, meta in zip(ladder.tiers, extra["tiers"]):
        saved = {k: int(v) for k, v in meta["bands"].items()}
        if saved != tier.bands:
            raise ValueError(
                f"ladder manifest is stale: tier {tier.name} was saved "
                f"with bands {saved}, the restored plan derives "
                f"{tier.bands} — rebuild the ladder for this plan")
    return ladder
