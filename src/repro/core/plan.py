"""ExecutionPlan — the typed parallel-execution plan for the whole stack.

The paper's contribution is a *communication structure* (one fused MHA+MLP
all-reduce per FAL block instead of preln's two), and the structure a run
uses is a property of the whole program, not of one call site.  This module
makes that structure an explicit, validated object:

    plan = ExecutionPlan.from_mesh(mesh, tp="explicit", sp=True)
    plan.validate(cfg)                      # loud errors, before tracing
    model.forward(params, cfg, batch, plan)

replacing the stringly-typed context dict that used to thread through
model, train, launch, and serving code unvalidated (its one-release
``from_legacy_dict`` shim has expired and is gone — ``resolve`` now
rejects dicts loudly).

Plan axes:

* ``phase``  — train | eval | prefill | decode | paged.  What used to be
  the ``mode=`` string argument of ``model.forward`` / ``blocks.block_apply``
  and the serving engines.
* ``tp``     — none | gspmd | explicit.  ``explicit`` routes the decoder
  family through the shard_map partial-sum stack
  (``models/model.py::decoder_stack_tp``) realising the paper's per-block
  collective fork; ``gspmd`` lets XLA shard against ``launch/mesh.py``'s
  PartitionSpecs.
* ``sequence_parallel`` — Megatron-SP-style LN regions under explicit TP:
  inter-block activations stay sharded over the model axis along the
  sequence dimension; blocks pay reduce-scatter/all-gather pairs instead of
  all-reduces (same reduce-collective count, per-block reduce bytes cut by
  ``tp_size``; ``models/blocks.py``).
* ``dual_branch`` — decode-time MHA||MLP branch parallelism: steady-state
  blocks compute the MLP branch from the (cached) first-attention signal
  concurrently with the attention branch's KV gather instead of serially
  after it (``models/blocks.py::_block_apply_dual``; the paper's "parallel
  execution of MHA and MLP" claim at serving time).  Valid only for
  decode/paged phases and connection modes whose MLP input is independent
  of the block's own attention (``core.fal.DUAL_BRANCH_MODES``).
* ``grad_compress`` — none | int8 | lowrank.  Opt-in compressed BACKWARD
  collectives under explicit TP: the forward psum/psum_scatter structure
  is untouched, but each one's transpose — the TP *gradient* all-reduce /
  all-gather — runs through ``optim/grad_compress.py``'s QSGD-int8 or
  PowerSGD-low-rank exchange (``compressed_psum`` /
  ``compressed_psum_scatter``), cutting measured gradient payload bytes
  ~4x for int8 (``bench_comm --json`` → ``grad_payload_bytes``).  Lossy
  by design, like the Fig 7 baselines; 'none' lowers byte-identical HLO.
* ``kernels`` — auto | pallas | interpret | ref.  Which implementation
  serves the paged-attention and page-copy dispatchers
  (``kernels/ops.py``): ``auto`` runs the compiled Pallas kernels on a TPU
  and the jnp oracles elsewhere; the other values pin one — the compiled
  kernel, the same kernel under the Pallas interpreter (CPU tests), or the
  gather-based oracle (the reference a chip run is checked against).

Inside the explicit-TP shard_map the blocks see ``plan.inner()`` — the same
plan with ``mesh=None`` and ``local_tp_size`` set; ``plan.tp_axis`` is then
the axis the partial-sum psums reduce over (None on replicated/GSPMD paths).
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Optional, Tuple


class Phase(enum.Enum):
    """Execution phase — what used to be the ``mode=`` string."""
    TRAIN = "train"
    EVAL = "eval"
    PREFILL = "prefill"
    DECODE = "decode"
    PAGED = "paged"

    @classmethod
    def coerce(cls, v) -> "Phase":
        if isinstance(v, Phase):
            return v
        try:
            return cls(v)
        except ValueError:
            raise ValueError(
                f"unknown phase {v!r}; valid: "
                f"{[p.value for p in cls]}") from None


#: phases that run the full-sequence block path (vs KV-cache decode/paged)
FULL_SEQUENCE_PHASES = (Phase.TRAIN, Phase.EVAL, Phase.PREFILL)


class TPStyle(enum.Enum):
    """Tensor-parallel style."""
    NONE = "none"
    GSPMD = "gspmd"
    EXPLICIT = "explicit"

    @classmethod
    def coerce(cls, v) -> "TPStyle":
        if isinstance(v, TPStyle):
            return v
        if v is None:
            return cls.NONE
        try:
            return cls(v)
        except ValueError:
            raise ValueError(
                f"unknown TP style {v!r}; valid: "
                f"{[t.value for t in cls]}") from None


#: ``ExecutionPlan.kernels`` values (``kernels.ops.kernel_args`` maps them)
KERNEL_PATHS = ("auto", "pallas", "interpret", "ref")

#: families with an explicit partial-sum TP stack (decoder_stack_tp)
EXPLICIT_TP_FAMILIES = ("dense", "moe", "vlm")

#: families whose decode path runs FAL transformer blocks and therefore has
#: a dual-branch (MHA||MLP) dispatch: the decoder family + the zamba hybrid
#: (its weight-shared attention block is a FAL block).  audio's decoder
#: blocks consume cross-attention (must assemble); ssm has no MHA/MLP fork.
DUAL_BRANCH_FAMILIES = ("dense", "moe", "vlm", "hybrid")


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Frozen description of how one program executes.

    ``mesh``/``data_axes``/``model_axis`` describe the device layout;
    ``local_tp_size`` is non-zero only on the plan a shard_map local body
    sees (``inner()``), where ``mesh`` is None by construction.
    """
    phase: Phase = Phase.TRAIN
    tp: TPStyle = TPStyle.NONE
    sequence_parallel: bool = False
    dual_branch: bool = False
    grad_compress: str = "none"            # none | int8 | lowrank
    kernels: str = "auto"                  # auto | pallas | interpret | ref
    mesh: Any = None                       # jax.sharding.Mesh | None
    data_axes: Tuple[str, ...] = ()
    model_axis: str = "model"
    local_tp_size: int = 0                 # set only by inner()

    # ------------------------------------------------------------- build --
    @classmethod
    def single_device(cls, phase=Phase.TRAIN,
                      dual_branch: bool = False) -> "ExecutionPlan":
        """Replicated single-program plan (no mesh, no TP)."""
        return cls(phase=Phase.coerce(phase), dual_branch=bool(dual_branch))

    @classmethod
    def from_mesh(cls, mesh, *, tp="gspmd", sp: bool = False,
                  phase=Phase.TRAIN, model_axis: str = "model",
                  data_axes: Optional[Tuple[str, ...]] = None,
                  dual_branch: bool = False,
                  grad_compress: str = "none") -> "ExecutionPlan":
        """Plan over ``mesh``.  ``data_axes`` defaults to every mesh axis
        except ``model_axis`` (so a ("pod", "data", "model") mesh composes
        pure DP across pods automatically)."""
        if data_axes is None:
            data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
        return cls(phase=Phase.coerce(phase), tp=TPStyle.coerce(tp),
                   sequence_parallel=bool(sp), mesh=mesh,
                   data_axes=tuple(data_axes), model_axis=model_axis,
                   dual_branch=bool(dual_branch),
                   grad_compress=str(grad_compress))

    @classmethod
    def resolve(cls, plan) -> "ExecutionPlan":
        """Entry-point coercion for every public API taking a plan.

        Accepts an ExecutionPlan, a Phase (or its string value — the old
        ``mode=`` calling convention), or None (single device, train).
        Context dicts (the pre-plan calling convention) are rejected
        loudly: their one-release ``from_legacy_dict`` shim has expired.
        """
        if isinstance(plan, ExecutionPlan):
            return plan
        if isinstance(plan, dict):
            raise TypeError(
                "context dicts are no longer accepted (the one-release "
                "shim expired); construct an ExecutionPlan (core.plan) — "
                "e.g. ExecutionPlan.from_mesh(mesh, tp='explicit')")
        phase = Phase.coerce(plan) if plan is not None else Phase.TRAIN
        return cls.single_device(phase)

    # -------------------------------------------------------- derived -----
    def with_phase(self, phase) -> "ExecutionPlan":
        return dataclasses.replace(self, phase=Phase.coerce(phase))

    def with_dual_branch(self, flag: bool = True) -> "ExecutionPlan":
        """Same plan with MHA||MLP decode branch parallelism toggled."""
        return dataclasses.replace(self, dual_branch=bool(flag))

    def with_grad_compress(self, method: str) -> "ExecutionPlan":
        """Same plan with compressed backward TP collectives selected."""
        return dataclasses.replace(self, grad_compress=str(method))

    def with_kernels(self, kernels: str) -> "ExecutionPlan":
        """Same plan with the kernel implementation pinned."""
        return dataclasses.replace(self, kernels=str(kernels))

    def inner(self) -> "ExecutionPlan":
        """The plan a shard_map local body sees: no mesh (collectives are
        explicit inside), ``local_tp_size`` pinned to the model-axis size."""
        return dataclasses.replace(self, mesh=None,
                                   local_tp_size=self.tp_size)

    @property
    def tp_size(self) -> int:
        if self.local_tp_size:
            return self.local_tp_size
        if self.mesh is not None and self.model_axis in self.mesh.axis_names:
            return int(self.mesh.shape[self.model_axis])
        return 1

    @property
    def tp_axis(self) -> Optional[str]:
        """Mesh axis name the block kernels psum partial sums over — set
        only INSIDE the explicit-TP shard_map; None on replicated/GSPMD
        paths (``blocks._assemble`` is then the identity)."""
        return self.model_axis if self.local_tp_size else None

    @property
    def use_explicit_tp(self) -> bool:
        """True when the caller asked for the explicit partial-sum TP path
        (shard_map over the block stack) instead of implicit GSPMD."""
        return self.tp is TPStyle.EXPLICIT and self.mesh is not None

    @property
    def is_sharded(self) -> bool:
        return self.mesh is not None

    @property
    def full_sequence(self) -> bool:
        return self.phase in FULL_SEQUENCE_PHASES

    @property
    def is_training_like(self) -> bool:
        """Train/eval: loss-path execution (e.g. the sharded-MoE dispatch
        is worth its collectives; decode token counts are not)."""
        return self.phase in (Phase.TRAIN, Phase.EVAL)

    # -------------------------------------------------------- validate ----
    def validate(self, cfg) -> "ExecutionPlan":
        """Fail loudly — before any tracing — when the plan cannot execute
        ``cfg``.  Returns self so call sites can chain."""
        if self.sequence_parallel and self.tp is not TPStyle.EXPLICIT:
            raise ValueError(
                "sequence_parallel=True requires tp='explicit': SP shards "
                "inter-block activations inside the explicit partial-sum "
                "shard_map stack; there is no GSPMD/replicated SP path")
        if self.sequence_parallel and self.phase not in FULL_SEQUENCE_PHASES:
            raise ValueError(
                f"sequence_parallel=True is a full-sequence layout "
                f"(train/eval/prefill); phase={self.phase.value} decodes "
                f"single tokens against KV caches")
        if self.dual_branch:
            self._validate_dual_branch(cfg)
        if self.kernels not in KERNEL_PATHS:
            raise ValueError(f"unknown kernels {self.kernels!r}; valid: "
                             f"{'/'.join(KERNEL_PATHS)}")
        if self.grad_compress not in ("none", "int8", "lowrank"):
            raise ValueError(
                f"unknown grad_compress {self.grad_compress!r}; valid: "
                f"none/int8/lowrank (optim/grad_compress.py methods)")
        if self.grad_compress != "none" and self.tp is not TPStyle.EXPLICIT:
            raise ValueError(
                "grad_compress != 'none' requires tp='explicit': the "
                "compressed collectives wrap the explicit-TP partial-sum "
                "psums (models/blocks.py); there is no GSPMD/replicated "
                "gradient-compression path")
        if self.tp is TPStyle.EXPLICIT:
            if self.mesh is None:
                raise ValueError("tp='explicit' requires a mesh (the "
                                 "explicit-TP stack shards over it)")
            if cfg.family not in EXPLICIT_TP_FAMILIES:
                raise ValueError(
                    f"tp='explicit': family '{cfg.family}' has no "
                    f"explicit-TP stack (decoder family only: "
                    f"{EXPLICIT_TP_FAMILIES}) — running it would silently "
                    f"fall back to GSPMD and mislabel any numbers")
            self._check_divisibility(cfg)
        if self.mesh is not None and cfg.holds_expert_share:
            raise ValueError(
                f"experts_held={cfg.experts_held} of n_experts="
                f"{cfg.n_experts}: a held expert share runs on a single "
                f"device; the expert-parallel paths on a mesh shard and "
                f"exchange every expert of the layer")
        if self.mesh is not None:
            names = tuple(self.mesh.axis_names)
            if self.model_axis not in names:
                raise ValueError(f"model_axis '{self.model_axis}' not in "
                                 f"mesh axes {names}")
            bad = [a for a in self.data_axes if a not in names]
            if bad:
                raise ValueError(f"data_axes {bad} not in mesh axes {names}")
        return self

    def _validate_dual_branch(self, cfg):
        """MHA||MLP branch parallelism exists only where the MLP input is
        independent of the block's own attention — fail loudly otherwise
        instead of silently running the sequential path and mislabeling any
        numbers collected under the plan."""
        from repro.core import fal  # core.fal pulls models.layers; keep lazy
        if self.phase not in (Phase.DECODE, Phase.PAGED):
            raise ValueError(
                f"dual_branch=True is a decode-time dispatch (decode/paged "
                f"phases); phase={self.phase.value} runs full-sequence "
                f"blocks whose collective structure is fixed by the "
                f"connection mode, not by branch scheduling")
        if cfg.family not in DUAL_BRANCH_FAMILIES:
            raise ValueError(
                f"dual_branch=True: family '{cfg.family}' has no MHA||MLP "
                f"decode dispatch ({DUAL_BRANCH_FAMILIES} only) — audio "
                f"decoder blocks consume cross-attention and ssm blocks "
                f"have no attention/MLP fork; running it would silently "
                f"fall back and mislabel any numbers")
        if cfg.connection not in fal.DUAL_BRANCH_MODES:
            raise ValueError(
                f"dual_branch=True requires a connection whose MLP input "
                f"is independent of the block's own attention "
                f"({'/'.join(fal.DUAL_BRANCH_MODES)}); "
                f"'{cfg.connection}' must assemble MHA output before the "
                f"MLP can start, so the branches cannot run concurrently")
        if cfg.post_norms:
            raise ValueError(
                "dual_branch=True: post_norms normalise the assembled "
                "attention output before the residual merge — the MLP "
                "branch cannot be issued concurrently with the KV gather")

    def _check_divisibility(self, cfg):
        """Explicit TP shards heads/hidden/experts evenly — fail loudly when
        the config doesn't divide (GSPMD pads; shard_map in_specs cannot)."""
        tp_size = self.tp_size

        def div(n, what):
            if n % tp_size:
                raise ValueError(f"explicit TP: {what}={n} is not divisible "
                                 f"by tp_size={tp_size}")
        div(cfg.n_heads, "n_heads")
        if not cfg.use_mla and cfg.n_kv_heads % tp_size \
                and tp_size % cfg.n_kv_heads:
            # n_kv_heads < tp_size is fine when groups align (KV
            # replication, attention._kv_group_slice); anything else cannot
            # shard evenly
            raise ValueError(f"explicit TP: n_kv_heads={cfg.n_kv_heads} "
                             f"divides neither way with tp_size={tp_size}")
        div(cfg.dense_d_ff or cfg.d_ff, "d_ff")
        if cfg.n_experts:
            div(cfg.n_experts, "n_experts")
            if cfg.n_shared_experts:
                div(cfg.moe_d_ff * cfg.n_shared_experts, "shared-expert d_ff")
