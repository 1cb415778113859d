"""fedlint site tables — the repo-specific knowledge the rules consult.

Every entry that EXEMPTS something carries a mandatory ``why`` string, so
the whitelist is self-documenting and reviewable the same way the
``# fedlint: disable=RULE -- reason`` suppressions are. Adding a new RNG
call site, config field, or carried-state key means either conforming to
the canonical pattern or extending these tables in the same diff — which
is exactly the review hook the rules exist to create.

Paths are repo-relative posix globs; ``func`` globs match the dotted
enclosing-function chain (``"FederationEngine._local_phase.one"`` style;
``""`` is module level, ``"*"`` matches any function including module
level).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

# fixed repo locations the repo-scope rules cross-check structurally
ENGINE_PATH = "src/repro/core/engine.py"
CONFIG_PATH = "src/repro/configs/base.py"
FEDERATION_PATH = "src/repro/checkpoint/federation.py"
# both user-facing drivers every ProxyFLConfig field must be threaded
# through (or be exempted below, with a why)
ENTRYPOINT_PATHS = ("src/repro/launch/train.py", "benchmarks/common.py")

KERNELS_GLOB = "src/repro/kernels/*.py"


@dataclasses.dataclass(frozen=True)
class Allow:
    """One whitelisted RNG site (see rules/rng_discipline.py)."""

    path: str           # repo-relative glob
    func: str           # dotted enclosing-function chain glob
    prims: Tuple[str, ...]  # of: "PRNGKey", "key", "split", "fold_in"
    why: str

    def __post_init__(self):
        assert self.why.strip(), "whitelist entries need a why"


# Canonical RNG sites. PRNGKey (root key creation), fold_in (stream
# derivation — the kill/resume schedule lives here) and split (chain
# advancement) may appear ONLY at these sites; anything new is a finding
# until it is consciously added here or rewritten against round_key /
# compress_round_key / fold_in(key, k).
RNG_ALLOWED_SITES: Tuple[Allow, ...] = (
    # --- THE canonical schedule sites the whole repo derives from -------
    Allow("src/repro/core/engine.py", "round_key", ("fold_in",),
          "THE per-round key schedule: fold_in(base, ROUND_KEY_OFFSET+t); "
          "every backend and every block size replays it bit-exactly"),
    Allow("src/repro/core/compress.py", "compress_round_key", ("fold_in",),
          "codec RNG domain: fold_in(round_key, COMPRESS_KEY_FOLD), "
          "disjoint from the per-client fold domain by construction"),
    Allow("src/repro/core/engine.py", "FederationEngine.init_states",
          ("fold_in",),
          "per-client init streams fold_in(key, k), k < ROUND_KEY_OFFSET — "
          "disjoint from the round-key domain (tests/test_rng_schedule.py)"),
    # --- engine round internals (one schedule, all backends) ------------
    Allow("src/repro/core/engine.py", "FederationEngine._round_loop",
          ("fold_in",),
          "loop backend's per-client round key fold_in(key, k) — must match "
          "the stacked backends' _local_phase fanout bit-for-bit"),
    Allow("src/repro/core/engine.py", "FederationEngine._local_phase*",
          ("fold_in", "split"),
          "stacked per-client key fanout + the in-step key,batch,noise "
          "split — the single local-trajectory definition all backends "
          "share"),
    Allow("src/repro/core/engine.py", "FederationEngine._one_step*",
          ("split",),
          "loop-backend one-step body: same key,batch,noise split as "
          "_local_phase so loop == vmap draws bit-identical batches"),
    Allow("src/repro/core/engine.py", "FederationEngine.restore_state",
          ("PRNGKey", "key"),
          "throwaway template init for the checkpoint tree structure; its "
          "values are fully overwritten by the loaded snapshot"),
    Allow("src/repro/core/engine.py", "_dml_state_init.init", ("split",),
          "per-client private/proxy init key pair"),
    # --- protocol / dp local steps --------------------------------------
    Allow("src/repro/core/protocol.py", "init_client", ("split",),
          "historical per-client private/proxy init key pair"),
    Allow("src/repro/core/protocol.py", "local_round", ("split",),
          "historical reference local round: key,batch,noise split"),
    Allow("src/repro/core/dp.py", "add_gaussian_noise", ("split",),
          "one noise key per leaf of the gradient tree"),
    Allow("src/repro/core/dp.py", "_flat_gaussian_like", ("split",),
          "bit-identical per-leaf normals to add_gaussian_noise, drawn "
          "for the fused flat kernel path"),
    # --- drivers (root keys + data derivation) --------------------------
    Allow("src/repro/core/baselines.py", "run_federated", ("PRNGKey", "key"),
          "the run's base key from the user seed; rounds derive via "
          "round_key"),
    Allow("src/repro/launch/train.py", "run",
          ("PRNGKey", "key", "fold_in"),
          "driver root key + per-client dataset streams fold_in(key, "
          "100+k)/fold_in(key, 999+k), outside the engine's fold domains"),
    Allow("src/repro/launch/serve.py", "main", ("PRNGKey", "key", "split"),
          "serving demo root key; decode loop advances by split"),
    Allow("src/repro/launch/steps.py", "init_train_state", ("split",),
          "LLM-scale per-client init key pair"),
    Allow("src/repro/launch/steps.py", "train_state_shapes",
          ("PRNGKey", "key"),
          "shape-only eval_shape probe; values never materialize"),
    Allow("src/repro/launch/steps.py", "serve_state_shapes",
          ("PRNGKey", "key"),
          "shape-only eval_shape probe; values never materialize"),
    Allow("src/repro/launch/steps.py", "make_round_block_step*",
          ("fold_in",),
          "dryrun round-block twin of the engine's in-scan round_key fold"),
    Allow("src/repro/launch/steps.py", "make_hier_round_block_step*",
          ("fold_in",),
          "two-level (hier) round-block twin: same fold_in(keys, t) "
          "per-round schedule as make_round_block_step, one shard per pod"),
    # --- module families with their own key ownership -------------------
    Allow("src/repro/nn/*.py", "*", ("split", "fold_in"),
          "parameter-init trees fan one init key out to sub-module inits; "
          "keys never escape the init call"),
    Allow("src/repro/data/*.py", "*", ("PRNGKey", "key", "split", "fold_in"),
          "dataset generation owns fixed task-seed domains (task identity "
          "must NOT depend on the sampling key; documented per function)"),
    Allow("benchmarks/*.py", "*", ("PRNGKey", "key", "split", "fold_in"),
          "figure drivers own their root seeds and synthetic-data "
          "streams; the engine rounds they invoke still derive keys via "
          "round_key"),
)


# Functions whose bodies are traced even though the module-local inference
# cannot see it (they are returned by factories and jitted by a caller, or
# called from inside another jitted program). Nested defs inherit.
TRACED_FUNCTION_SITES: Tuple[Tuple[str, str], ...] = (
    ("src/repro/core/engine.py", "FederationEngine._local_phase*"),
    ("src/repro/core/engine.py", "FederationEngine._round_core*"),
    ("src/repro/core/engine.py", "FederationEngine._stale_round_core*"),
    ("src/repro/core/engine.py", "FederationEngine._hier_round_core*"),
    ("src/repro/core/engine.py", "FederationEngine._build_block*"),
    ("src/repro/core/engine.py", "FederationEngine._one_step*"),
    ("src/repro/core/engine.py", "FederationEngine._mix_matmul_op*"),
    ("src/repro/core/engine.py", "FederationEngine._shard_mix_op*"),
    ("src/repro/core/engine.py", "classifier_sampler*"),
    ("src/repro/core/gossip.py", "pushsum_mix"),
    ("src/repro/core/gossip.py", "pushsum_mix_debiased"),
    ("src/repro/core/gossip.py", "stale_mix_apply"),
    ("src/repro/core/gossip.py", "_hier_intra"),
    ("src/repro/core/gossip.py", "hier_mix_debiased"),
    ("src/repro/core/gossip.py", "hier_stale_mix_apply"),
    ("src/repro/core/gossip.py", "debias"),
    ("src/repro/core/gossip.py", "pushsum_gossip_shard"),
    ("src/repro/core/compress.py", "_topk_encode_decode"),
    ("src/repro/core/compress.py", "_int8_encode_decode"),
    ("src/repro/core/compress.py", "encode_decode"),
    ("src/repro/core/compress.py", "_split_P"),
    ("src/repro/core/compress.py", "_ef_encode"),
    ("src/repro/core/compress.py", "compressed_pushsum_mix"),
    ("src/repro/core/compress.py", "compressed_stale_mix"),
    ("src/repro/core/protocol.py", "dml_step_fn*"),
    ("src/repro/core/protocol.py", "ce_step_fn*"),
    ("src/repro/core/protocol.py", "_eval_apply*"),
    ("src/repro/core/dp.py", "clip_by_global_norm"),
    ("src/repro/core/dp.py", "add_gaussian_noise"),
    ("src/repro/core/dp.py", "_flat_gaussian_like"),
    ("src/repro/core/dp.py", "dp_gradient*"),
    ("src/repro/core/dp.py", "dp_adam_update*"),
)


# ProxyFLConfig fields exempt from the entry-point threading check of
# FED004 (fingerprint-coverage). Empty today: every field IS threaded
# through launch/train.py and benchmarks/common.py. Add entries as
# {"field": "why"} — the why is mandatory and shows up in --list-rules.
FLAG_EXEMPT_FIELDS: dict = {}


# Federation-level scan-carry keys exempt from FED003 (carry-coverage).
# Empty today: stale_theta/stale_w/ef_state/hier_buffer/hier_w all ride
# _ckpt_payload. Note the verifiable-federation layer (PR 10) adds NO
# carried state — commitment records (audit.jsonl, the meta commitment
# stamps) are on-disk audit artifacts recomputed from the canonical
# payload, never scan-carries, so they are outside FED003's domain by
# construction (see docs/INVARIANTS.md, "Commitment chain").
CARRY_EXEMPT_KEYS: dict = {}
