"""Static Mosaic tile-rule validation for Pallas kernel specs (MXL-K).

The round-5 AOT audit proved the most expensive class of bug in this
repo is statically detectable: the ring-attention flash kernel's lse
output block was a 1-D ``(block_q,)`` stats row — Mosaic requires the
last two block dims to tile to the dtype's minimum tile, so the kernel
had never compiled for a real TPU, and nothing said so until a chip
window was spent discovering it.  This pass re-derives Mosaic's layout
rules from the Pallas guide and applies them to a *declared* description
of every kernel's BlockSpecs, with zero chip time and zero compiler
invocations:

- minimum tile by dtype on the last two (sublane, lane) dims of each
  block: (8, 128) float32, (16, 128) bfloat16, (32, 128) int8/fp8 —
  a partial tiling must be a multiple of the granule; a block covering
  the whole array dim is legal at any size (Mosaic pads it);
- a block must have at least two non-squeezed dims (the lse bug: a 1-D
  stats row cannot be a TPU output block — broadcast it across a
  128-lane dim instead);
- the lane (last) dim of a partial tiling must be a multiple of 128;
- grid divisibility: an array dim not divisible by its block dim makes
  the trailing grid step compute padding (warning, not error — Mosaic
  masks it, you just pay for dead lanes);
- containment: a block dim may not exceed its array dim.

Kernels declare themselves through :func:`register_kernel_spec` — the
module defining the ``pallas_call`` registers a provider returning one
or more spec dicts built from the SAME shape arithmetic the call uses
(see ``parallel/ring_attention.flash_kernel_spec``), so every BlockSpec
in the repo is checked on each ``Symbol.validate()`` / ``mxlint`` run.
``rtc.Rtc`` checks its whole-array blocks at build time through
:func:`block_findings` (knob: ``MXTPU_RTC_LINT``).

A spec dict::

    {"name": "flash_forward",
     "origin": "mxnet_tpu/parallel/ring_attention.py",
     "grid": (8, 4),
     "blocks": [{"role": "in", "name": "q",
                 "block": (None, 128, 64),     # None = squeezed dim
                 "array": (8, 512, 64),
                 "dtype": "float32"}, ...]}
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as _np

from .core import register_rule

__all__ = ["LANES", "min_tile", "KERNEL_SPECS", "register_kernel_spec",
           "unregister_kernel_spec", "block_findings", "spec_findings",
           "kernel_spec_issues"]

LANES = 128
# itemsize -> minimum sublane count (packing: narrower types stack more
# rows into one 32-bit-deep vreg sublane)
_MIN_SUBLANES = {8: 4, 4: 8, 2: 16, 1: 32}


def min_tile(dtype):
    """Mosaic minimum tile (sublanes, lanes) for ``dtype``."""
    itemsize = _np.dtype(dtype or _np.float32).itemsize
    return (_MIN_SUBLANES.get(itemsize, 8), LANES)


# ----------------------------------------------------------------------
# kernel spec registry
# ----------------------------------------------------------------------
KERNEL_SPECS = OrderedDict()    # name -> provider() -> spec dict | [dict]


def register_kernel_spec(name, provider):
    """Register a Pallas kernel's block layout for static validation.

    ``provider`` is a zero-arg callable returning a spec dict (or list
    of them) — lazy so registration at import time stays free — or the
    spec itself.  Re-registering a name overwrites (idempotent module
    re-import)."""
    if not callable(provider):
        spec = provider
        provider = lambda: spec     # noqa: E731
    KERNEL_SPECS[name] = provider
    return provider


def unregister_kernel_spec(name):
    KERNEL_SPECS.pop(name, None)


def _ensure_builtin_specs():
    """Import the modules that define in-tree Pallas kernels so their
    registrations exist even when the caller never touched them."""
    try:
        from ..parallel import ring_attention  # noqa: F401
    except Exception:
        pass
    try:
        from .. import kernels  # noqa: F401  (quantize/fused_opt)
    except Exception:
        pass


# ----------------------------------------------------------------------
# block validation
# ----------------------------------------------------------------------
def block_findings(block, array, dtype, label="block"):
    """Validate one block against one array; returns a list of
    ``(rule_id, severity, message)``.

    ``block`` entries are ints or None (squeezed dims, pallas
    ``BlockSpec((None, bq, d), ...)`` style); ``block=None`` means the
    whole array is one block (the rtc path)."""
    findings = []
    array = tuple(int(d) for d in array)
    if block is None:
        block = array
    block = tuple(block)
    if len(block) != len(array):
        findings.append((
            "MXL-K004", "error",
            "%s: block rank %d != array rank %d"
            % (label, len(block), len(array))))
        return findings
    # containment + the positions of the non-squeezed dims
    kept = []               # (array_dim_index, block_extent)
    for i, b in enumerate(block):
        if b is None:
            continue
        b = int(b)
        if b > array[i]:
            findings.append((
                "MXL-K004", "error",
                "%s: block dim %d (%d) exceeds array dim (%d)"
                % (label, i, b, array[i])))
        elif array[i] % b:
            pad_steps = -array[i] % b
            findings.append((
                "MXL-K003", "warning",
                "%s: array dim %d (%d) is not divisible by block (%d): "
                "the trailing grid step computes %d padded rows"
                % (label, i, array[i], b, pad_steps)))
        kept.append((i, b))
    if len(kept) < 2:
        findings.append((
            "MXL-K001", "error",
            "%s: block has %d tileable dim(s) after squeezing — Mosaic "
            "tiles the last two dims to (sublane, %d) and a %d-D block "
            "cannot be laid out; broadcast stats across a %d-lane dim "
            "instead (the historical flash-lse bug)"
            % (label, len(kept), LANES, len(kept), LANES)))
        return findings
    sub_need, lane_need = min_tile(dtype)
    (lane_i, lane_b) = kept[-1]
    (sub_i, sub_b) = kept[-2]
    # a block covering its whole array dim is legal at any size (Mosaic
    # pads the tail tile); a PARTIAL tiling must align to the granule
    if lane_b != array[lane_i] and lane_b % lane_need:
        findings.append((
            "MXL-K002", "error",
            "%s: lane (last) block dim %d is neither the full array dim "
            "(%d) nor a multiple of %d — Mosaic cannot window the lane "
            "axis off-granule" % (label, lane_b, array[lane_i], lane_need)))
    if sub_b != array[sub_i] and sub_b % sub_need:
        findings.append((
            "MXL-K001", "error",
            "%s: sublane block dim %d is neither the full array dim (%d) "
            "nor a multiple of the %s minimum tile (%d, %d)"
            % (label, sub_b, array[sub_i],
               _np.dtype(dtype or _np.float32).name, sub_need, lane_need)))
    return findings


def spec_findings(spec):
    """Validate one kernel spec dict; ``(rule_id, severity, message)``
    list, each message prefixed with the kernel name."""
    findings = []
    name = spec.get("name", "<kernel>")
    grid = spec.get("grid")
    if grid is not None and any(int(g) <= 0 for g in grid):
        findings.append(("MXL-K003", "warning",
                         "kernel %s: grid %s has a non-positive extent"
                         % (name, tuple(grid))))
    for blk in spec.get("blocks", ()):
        label = "kernel %s, %s block %r" % (
            name, blk.get("role", "in"), blk.get("name", "?"))
        findings.extend(block_findings(blk.get("block"), blk["array"],
                                       blk.get("dtype"), label=label))
    return findings


def kernel_spec_issues():
    """Validate every registered kernel spec.

    Returns ``[(kernel_name, rule_id, severity, message)]``; a provider
    that raises contributes one MXL-K004 error (a spec that cannot even
    be built is a broken registration, not a pass)."""
    _ensure_builtin_specs()
    out = []
    for name, provider in KERNEL_SPECS.items():
        try:
            specs = provider()
        except Exception as exc:  # noqa: BLE001
            out.append((name, "MXL-K004", "error",
                        "kernel spec provider %r failed: %s" % (name, exc)))
            continue
        if isinstance(specs, dict):
            specs = [specs]
        for spec in specs:
            for rule_id, sev, msg in spec_findings(spec):
                out.append((name, rule_id, sev, msg))
    return out


# ----------------------------------------------------------------------
# the MXL-K rules
# ----------------------------------------------------------------------
def _findings_by_rule(ctx):
    if "kernel_findings" not in ctx.cache:
        by_rule = {}
        if ctx.target == "tpu":
            for _name, rule_id, sev, msg in kernel_spec_issues():
                by_rule.setdefault(rule_id, []).append((sev, msg))
        ctx.cache["kernel_findings"] = by_rule
    return ctx.cache["kernel_findings"]


def _report_rule(ctx, rule_id):
    for sev, msg in _findings_by_rule(ctx).get(rule_id, ()):
        ctx.report(None, msg, severity=sev, rule_id=rule_id)


@register_rule("MXL-K001", "error",
               doc="pallas block violates the Mosaic dtype minimum tile")
def _rule_k001(ctx):
    _report_rule(ctx, "MXL-K001")


@register_rule("MXL-K002", "error",
               doc="pallas block lane dim not 128-aligned")
def _rule_k002(ctx):
    _report_rule(ctx, "MXL-K002")


@register_rule("MXL-K003", "warning",
               doc="pallas grid padding: array dim not divisible by block")
def _rule_k003(ctx):
    _report_rule(ctx, "MXL-K003")


@register_rule("MXL-K004", "error",
               doc="pallas block exceeds its array (or spec is malformed)")
def _rule_k004(ctx):
    _report_rule(ctx, "MXL-K004")
