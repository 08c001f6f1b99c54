"""Operation and byte counts, from shapes alone.

``asm_relu_cost`` gives a Pallas kernel's operations and bytes from its
operand shapes as they appear in a compiled program: the least work the
kernel's algorithm needs, so that the least time they imply bounds the
measured time from below.  A model's FLOPs are its architecture
module's (``archs/<arch>.py``, ``model_flops``).
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "s8": 1, "u8": 1,
                "f8e4m3fn": 1}


def parse_shape(text: str) -> tuple[str, tuple[int, ...]]:
    """``"f32[8192,64]{1,0}"`` -> ``("f32", (8192, 64))``."""
    m = re.match(r"(\w+)\[([\d,]*)\]", text.strip())
    if m is None:
        raise ValueError(f"not an array shape: {text!r}")
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def _elems(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def in_hbm(shape: str) -> bool:
    """Whether an array with this compiled layout lives in HBM: TPU layouts
    mark other memory spaces (``S(1)`` is VMEM) and leave HBM unmarked."""
    m = re.search(r"S\((\d+)\)", shape)
    return m is None or m.group(1) == "0"


def _hbm_bytes(shape: str) -> int:
    dtype, dims = parse_shape(shape)
    return _DTYPE_BYTES[dtype] * _elems(dims) if in_hbm(shape) else 0


def asm_relu_cost(operands: list[str], output: str) -> tuple[float, float]:
    """``kernels/asm_relu.py``: per row of ``nf`` coefficients, three
    products with 64-wide reconstruction matrices (mask approximation,
    exact reconstruction, forward transform): ``6·rows·nf·64`` FLOPs.
    Bytes: what the kernel must move through HBM, the operands and the
    output that the compiled program keeps there (each once); an array
    the program placed in VMEM costs no HBM traffic."""
    _dtype, (rows, nf) = parse_shape(operands[0])
    flops = 6.0 * rows * nf * 64
    nbytes = _hbm_bytes(output) + sum(_hbm_bytes(o) for o in operands)
    return flops, float(nbytes)


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least seconds on a chip with ``peak``, and the term that bounds it."""
    t_ops = flops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


_DEF = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = (\S+) ")
_CALL = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = (\S+) custom-call\(([^)]*)\)")


def custom_calls(hlo_text: str) -> list[dict]:
    """Mosaic kernels (``tpu_custom_call``) of a compiled program's text:
    instruction name, the jitted function that issued it (from
    ``op_name``), and the compiled shapes, memory space included, of its
    output and operands."""
    shapes = {}
    for line in hlo_text.splitlines():
        m = _DEF.match(line)
        if m:
            shapes[m.group(1)] = m.group(2)
    out = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _CALL.match(line)
        if m is None:
            continue
        names = [re.sub(r"/\*.*?\*/", "", a).strip().lstrip("%")
                 for a in m.group(3).split(",")]
        src = re.search(r'op_name="([^"]*)"', line)
        out.append({"name": m.group(1), "output": m.group(2),
                    "operands": [shapes[n] for n in names],
                    "op_name": src.group(1) if src else ""})
    return out


def module_name(hlo_text: str) -> str:
    """The ``HloModule`` name of a compiled program's text."""
    m = re.search(r"^HloModule (\S+?),", hlo_text, re.M)
    if m is None:
        raise ValueError("no HloModule line")
    return m.group(1)
