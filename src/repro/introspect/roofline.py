"""Roofline math over pluggable hardware profiles.

This is the promotion of the roofline model that used to live (with
hardcoded TPU v5e constants) in ``benchmarks/roofline.py``: a registry
of :class:`HardwareProfile` peak numbers keyed by name, resolved from —
in priority order — an explicit spec (CLI flag), the ``JPEG_HW_PROFILE``
environment variable, a caller default, or the ``device_kind`` of the
first JAX device.  A device kind missing from ``DEVICE_KINDS`` is an
error, never a guessed profile.
``benchmarks/roofline.py`` is now a thin shim over this module.

A profile spec is either a registry name (``tpu-v5e``, ``cpu``, ...) or
a custom ``peak_flops,hbm_bw,link_bw`` triple of floats, e.g.
``JPEG_HW_PROFILE=1.97e14,8.19e11,5e10``.

:func:`roofline` turns an HLO cost (FLOPs / anchor bytes / collective
bytes, e.g. from ``launch.hlo_analysis.analyze_hlo``) into the three
roofline terms and the dominant one — ``compute`` (FLOP-bound),
``memory`` (HBM-bound) or ``collective`` (interconnect-bound) — plus
the predicted latency (the max term: perfect overlap is assumed, so
this is a *lower bound* the measured wall is compared against).
"""
from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = [
    "HardwareProfile",
    "PROFILES",
    "DEVICE_KINDS",
    "detect_device_kind",
    "resolve_profile",
    "roofline",
]


@dataclass(frozen=True)
class HardwareProfile:
    """Peak rates a roofline prediction divides by.

    ``peak_flops`` — peak dense f32/bf16 FLOP/s per device;
    ``hbm_bw`` — main-memory bandwidth, bytes/s;
    ``link_bw`` — per-device interconnect bandwidth, bytes/s.
    """

    name: str
    peak_flops: float
    hbm_bw: float
    link_bw: float

    def to_json(self) -> dict:
        return {"name": self.name, "peak_flops": self.peak_flops,
                "hbm_bw": self.hbm_bw, "link_bw": self.link_bw}


# Registry of known profiles.  TPU numbers are the published per-chip
# peaks; the ``cpu`` entry is an order-of-magnitude stand-in for a
# few-core AVX host (CI) — roofline predictions there are for *ranking*
# blocks and spotting anomalies, not absolute-latency promises.
PROFILES: dict[str, HardwareProfile] = {
    "tpu-v5e": HardwareProfile("tpu-v5e", 197e12, 819e9, 50e9),
    "tpu-v4": HardwareProfile("tpu-v4", 275e12, 1228e9, 50e9),
    "gpu": HardwareProfile("gpu", 60e12, 1000e9, 25e9),
    "cpu": HardwareProfile("cpu", 100e9, 30e9, 10e9),
}

#: ``jax.Device.device_kind`` → registry key.  TPU v5e reports itself as
#: "TPU v5 lite".
DEVICE_KINDS = {
    "TPU v5 lite": "tpu-v5e",
    "TPU v4": "tpu-v4",
    "cpu": "cpu",
}

ENV_VAR = "JPEG_HW_PROFILE"


def detect_device_kind() -> str:
    """``device_kind`` of the first JAX device (e.g. ``TPU v5 lite``)."""
    import jax

    return jax.devices()[0].device_kind


def _parse_spec(spec: str) -> HardwareProfile:
    spec = spec.strip()
    if spec in PROFILES:
        return PROFILES[spec]
    parts = [p.strip() for p in spec.split(",")]
    if len(parts) == 3:
        try:
            flops, hbm, link = (float(p) for p in parts)
        except ValueError:
            pass
        else:
            return HardwareProfile("custom", flops, hbm, link)
    raise ValueError(
        f"unknown hardware profile {spec!r}: want one of "
        f"{sorted(PROFILES)} or a 'peak_flops,hbm_bw,link_bw' triple")


def resolve_profile(spec: str | None = None, *,
                    default: str | None = None) -> HardwareProfile:
    """Resolve the hardware profile to predict against.

    Priority: explicit ``spec`` (CLI) > ``JPEG_HW_PROFILE`` env var >
    ``default`` registry name > the detected ``device_kind``
    (:data:`DEVICE_KINDS`; an unlisted kind raises ``ValueError``).
    ``spec`` and the env var accept a registry name or a custom
    ``peak_flops,hbm_bw,link_bw`` triple.
    """
    if spec:
        return _parse_spec(spec)
    env = os.environ.get(ENV_VAR)
    if env:
        return _parse_spec(env)
    if default is not None:
        return PROFILES[default]
    kind = detect_device_kind()
    if kind not in DEVICE_KINDS:
        raise ValueError(
            f"no hardware profile for device_kind {kind!r} (known: "
            f"{sorted(DEVICE_KINDS)}); pass a profile name or a "
            f"'peak_flops,hbm_bw,link_bw' triple")
    return PROFILES[DEVICE_KINDS[kind]]


def roofline(flops: float, bytes_: float, collective_bytes: float,
             profile: HardwareProfile) -> dict:
    """The three roofline terms and the dominant one.

    Returns ``{"compute_s", "memory_s", "collective_s", "predicted_s",
    "term"}`` where ``predicted_s`` is the max term and ``term`` names
    it (``compute`` / ``memory`` / ``collective``).
    """
    terms = {
        "compute": flops / profile.peak_flops,
        "memory": bytes_ / profile.hbm_bw,
        "collective": collective_bytes / profile.link_bw,
    }
    dominant = max(terms, key=lambda k: terms[k])
    return {
        "compute_s": terms["compute"],
        "memory_s": terms["memory"],
        "collective_s": terms["collective"],
        "predicted_s": terms[dominant],
        "term": dominant,
    }
