"""Device-memory budget discipline: block-sized device uploads on the
storage/query serving path must go through the shared HBM budget
(utils/hbm.py), because a raw `jax.device_put` pins device memory no
budget sees — enough of them and the resident caches' ceilings are
meaningless (the budget reclaims what it knows about while untracked
buffers OOM the chip anyway).

Rules:
  unbudgeted-device-put   a raw `jax.device_put(...)` call inside the
                          storage / query / ops / parallel modules — the
                          layers that move block-sized arrays (sealed
                          blocks, consolidated grids, flush tiles) onto
                          devices. Route uploads through a cache
                          registered with `utils.hbm.HBMBudget` (the
                          upload/derived grid caches, the block cache),
                          or carry a justified suppression (the
                          mesh-flush staging path deliberately stages
                          transient tiles that the encode program
                          consumes and frees before returning).
"""

from __future__ import annotations

import ast
from typing import Iterator, List

from .core import Finding, Module, Rule, qualname


class UnbudgetedDevicePutRule(Rule):
    """unbudgeted-device-put: raw jax.device_put on the serving path."""

    id = "unbudgeted-device-put"
    severity = "error"
    dirs = ("storage", "query", "ops", "parallel")
    requires_import = "jax"

    def _is_device_put(self, call: ast.Call, mod: Module) -> bool:
        q = qualname(call.func)
        if q == "jax.device_put":
            return True
        if q == "device_put" and self._imported_from_jax(mod):
            return True
        return False

    @staticmethod
    def _imported_from_jax(mod: Module) -> bool:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom) and node.module == "jax":
                if any(a.name == "device_put" for a in node.names):
                    return True
        return False

    def _aliases(self, mod: Module) -> set:
        """Names bound to jax.device_put at module level
        (`put = jax.device_put`): calls through the alias pin device
        memory just the same."""
        out = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign) and \
                    qualname(node.value) == "jax.device_put":
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        out.add(tgt.id)
        return out

    def check(self, mod: Module) -> Iterator[Finding]:
        aliases = self._aliases(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            direct = self._is_device_put(node, mod)
            q = qualname(node.func)
            aliased = q in aliases
            if not (direct or aliased):
                continue
            yield self.finding(
                mod, node,
                "raw jax.device_put pins device memory no budget sees; "
                "route through a cache registered with utils.hbm."
                "HBMBudget, or suppress with a justification "
                "for transient staging the program frees itself")


RULES: List[Rule] = [UnbudgetedDevicePutRule()]
