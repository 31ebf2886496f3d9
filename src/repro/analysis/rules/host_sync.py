"""Host-sync discipline for the streaming hot path.

The device-resident stream contract is that a steady-state frame moves
exactly three things across the host<->device boundary: the new frame in,
the step's scalar verdict out, and the decoded survivor slot list out.
Everything else — reference pixels, survivor bitmaps, drift, frame
counters — stays on device inside the donated
:class:`repro.stream.StreamState`.

``HOST_SYNC`` keeps that contract visible in the diff: any host
materialisation (``np.asarray``/``np.array``, ``jax.device_get``,
``.item()``) inside ``stream/engine.py`` or ``stream/video.py`` must
carry a ``# repro: ignore[HOST_SYNC] <why>`` justification naming which
side of the contract it is (frame intake, scalar verdict, slot decode,
keyframe upload) — an unjustified one is a new synchronisation point
someone smuggled into the hot path.
"""

from __future__ import annotations

import ast

from ..core import Finding, Rule, SourceFile, register

# the device-resident hot path: every host materialisation here is a
# potential per-frame sync and must be one of the contract's endpoints
_HOT_FILES = ("stream/engine.py", "stream/video.py")
_NP_NAMES = ("np", "numpy")
_NP_FUNCS = ("asarray", "array")


@register
class HostSyncRule(Rule):
    id = "HOST_SYNC"
    summary = ("host materialisation (np.asarray/np.array/jax.device_get/"
               ".item()) in the streaming hot path without a justified "
               "suppression")

    def check(self, src: SourceFile, project) -> list[Finding]:
        if not src.rel.endswith(_HOT_FILES):
            return []
        findings = []

        def flag(node: ast.expr, what: str) -> None:
            findings.append(Finding(
                src.rel, node.lineno, node.col_offset + 1, self.id,
                f"{what} in the streaming hot path is a host sync / "
                f"host-side materialisation; keep stream state "
                f"device-resident, or justify which endpoint of the "
                f"transfer contract this is with "
                f"`# repro: ignore[HOST_SYNC] <why>`"))

        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not isinstance(fn, ast.Attribute):
                continue
            if fn.attr in _NP_FUNCS and isinstance(fn.value, ast.Name) \
                    and fn.value.id in _NP_NAMES:
                flag(node, f"{fn.value.id}.{fn.attr}(...)")
            elif fn.attr == "device_get":
                flag(node, f"{fn.attr}(...)")
            elif fn.attr == "item" and not node.args and not node.keywords:
                flag(node, ".item()")
        return findings
