"""FedARA strategy: binds truncated-SVD adaptation, dynamic rank allocation
and rank-based module pruning into client/server hooks (paper Algorithm 1).

The federated runtime (repro.federated.server) is strategy-agnostic; every
baseline implements this same interface (repro.federated.baselines).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from repro.core import adapters as AD
from repro.core import arbitration as ARB
from repro.core import comm as COMM
from repro.core import importance as IMP
from repro.core import masks as MK
from repro.core import pruning as PR
from repro.core import schedule as SCH


@dataclasses.dataclass
class Strategy:
    """Base strategy = plain FedPEFT (no rank allocation)."""
    name: str = "fedlora"
    peft: str = AD.LORA
    dtype_bytes: int = 4

    # ---- hooks -------------------------------------------------------------
    def init_rank(self, cfg) -> int:
        return cfg.adapter_rank

    def post_init(self, model, base, trainable, key):
        """Strategy-specific (re)initialization (FeDeRA/SLoRA/FFA-dr).
        Returns (base, trainable) — FeDeRA also rewrites the base."""
        return base, trainable

    def uses_masks(self) -> bool:
        return False

    def stage1_rounds(self, total_rounds: int) -> int:
        """Rounds of a stage run before the adapter rounds (SLoRA's)."""
        return 0

    def budget(self, rnd: int) -> int | None:
        return None

    def local_masks(self, rnd: int, adapters, grads, n_modules_ranks: int):
        return None

    def device_importance(self) -> str | None:
        """The importance method of ``local_masks`` when a device program
        can score each client alone (``IMP.score_flat``), else None."""
        return None

    def arbitrate(self, rnd: int, local_masks, prev_global):
        return prev_global

    def arbitrate_votes(self, rnd: int, vote_sums, n_reporting, prev_global):
        """Aggregate-only arbitration (secure aggregation hands the server
        vote *sums*, never per-client masks)."""
        return prev_global

    def optimizer_gate(self, trainable, masks):
        """0/1 pytree over trainable leaves (FFA freezes A; RankDet gates)."""
        return None

    def comm_down(self, trainable, masks) -> int:
        return COMM.count_params(trainable.get("adapters", {}), masks) \
            * self.dtype_bytes + self._head_bytes(trainable)

    def comm_up(self, trainable, masks) -> int:
        return self.comm_down(trainable, masks)

    def _head_bytes(self, trainable) -> int:
        head = trainable.get("head")
        if not head:
            return 0
        return sum(int(np.prod(v.shape)) for v in head.values()) * self.dtype_bytes


@dataclasses.dataclass
class FedARA(Strategy):
    """The paper's strategy (Algorithm 1)."""
    name: str = "fedara"
    peft: str = AD.BEA
    importance: str = IMP.MAG
    threshold: float = 0.5                 # T_h
    target_rank_frac: float = 0.25         # T_r = r0/4 (paper §V)
    warmup_rounds: int = 5
    final_rounds_frac: float = 0.5         # decay ends at round T/2 (paper)
    total_rounds: int = 100
    module_pruning: bool = True
    n_experts: int = 0

    _ema: Any = None

    def uses_masks(self) -> bool:
        return True

    def budget_params(self, n_rank_units: int):
        b0 = n_rank_units
        return dict(b0=b0,
                    b_target=int(b0 * self.target_rank_frac),
                    t_warmup=self.warmup_rounds,
                    t_final=int(self.total_rounds * self.final_rounds_frac),
                    total_rounds=self.total_rounds)

    def budget(self, rnd: int, n_rank_units: int | None = None) -> int | None:
        if n_rank_units is None:
            return None
        return SCH.rank_budget(rnd, **self.budget_params(n_rank_units))

    def local_masks(self, rnd: int, adapters, grads, n_rank_units: int):
        scores, self._ema = IMP.score_tree(
            adapters, grads, self.importance, n_experts=self.n_experts,
            ema_state=self._ema)
        b = self.budget(rnd, n_rank_units)
        return MK.generate_local_masks(scores, b)

    def device_importance(self) -> str | None:
        # Sensitivity's EMA runs across clients, so only the host has it
        return None if self.importance == IMP.SENSITIVITY else self.importance

    def masks_from_scores(self, rnd: int, flat, layout, n_rank_units: int):
        """``local_masks`` from one client's scores already computed
        (``IMP.score_flat``), laid out as ``layout``."""
        return MK.top_budget(flat, layout, self.budget(rnd, n_rank_units))

    def arbitrate(self, rnd: int, local_masks, prev_global):
        if not local_masks:
            return prev_global
        return ARB.arbitrate(local_masks, self.threshold, prev_global)

    def arbitrate_votes(self, rnd: int, vote_sums, n_reporting, prev_global):
        if vote_sums is None or n_reporting <= 0:
            return prev_global
        return ARB.arbitrate_from_votes(vote_sums, n_reporting,
                                        self.threshold, prev_global)

    def optimizer_gate(self, trainable, masks):
        if not self.module_pruning or masks is None:
            return None
        gate = PR.trainable_gate(trainable.get("adapters", {}), masks)
        out = {"adapters": gate}
        if "head" in trainable:
            import jax
            import jax.numpy as jnp
            out["head"] = jax.tree.map(
                lambda v: jnp.ones((), jnp.float32), trainable["head"])
        return out

    def comm_down(self, trainable, masks) -> int:
        return COMM.bytes_down(trainable.get("adapters", {}), masks,
                               self.dtype_bytes,
                               ) + self._head_bytes(trainable)

    def comm_up(self, trainable, masks) -> int:
        return self.comm_down(trainable, masks)


@dataclasses.dataclass
class FedSVD(Strategy):
    """Ablation: truncated-SVD adaptation without dynamic rank allocation."""
    name: str = "fedsvd"
    peft: str = AD.BEA
