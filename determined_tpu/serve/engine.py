"""ServingEngine — checkpoint → AOT-compiled prefill/decode executables.

Owns everything device-side for one serve replica:

  - loads a **COMPLETED** checkpoint through the integrity protocol
    (manifest + COMMIT verified before a single byte is trusted; a corrupt
    latest checkpoint falls back through the COMPLETED lineage exactly
    like `Trainer._restore`),
  - keeps the weights resident in the serving dtype: what is wider is
    narrowed once, when the engine is built (`serve/model.py
    resident_params`), so no call casts a weight again,
  - AOT-compiles the decode step once and the prefill step per prompt
    bucket (`jit(...).lower(...).compile()`), so no request ever pays a
    trace — the serving analogue of the trial preflight discipline:
    all compilation happens before the first request is admitted,
  - holds the cache — a pytree of pools that is the family's own: the
    paged KV pool, and for a family with recurrent state a state pool
    indexed by lane beside it (donated through every call: one copy in
    HBM) —, the per-slot block tables and a step-folded sampling rng.

The engine is one for every family: what is a family's own — the
resident tree, the cache, prefill, the decode step, the block copy — it
takes from the family's serving module (`family_of`: `serve/model.py` for
GPT-2, `serve/falcon_h1.py`, `serve/glm4_moe_lite.py`).

The engine is intentionally single-consumer: only the batcher thread
(scheduler.py) calls prefill/decode. `stats()` reads lock-free host
counters and, for a family whose cache holds a counter (the experts'
load), makes the one device read there is outside the batcher's thread.
"""

from __future__ import annotations

import importlib
import logging
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from determined_tpu.common import trace
from determined_tpu.expconf import SERVING_FAMILIES
from determined_tpu.ops.paged_attention import live_spans
from determined_tpu.ops.ssm_state import live_lanes
from determined_tpu.parallel.sharding import LogicalRules

logger = logging.getLogger("determined_tpu.serve")

DEFAULT_BUCKETS = (32, 64, 128, 256, 512, 1024)

def family_module(name: str):
    """The serving module of the family `serving.model` names
    (`expconf.SERVING_FAMILIES`): `config_from`, `resident_params`,
    `init_cache` / `cache_bytes` / `state_bytes`, `prefill`,
    `decode_step`, `copy_block` (None where no block can be shared),
    `sample`, `kernel_refusal`, `adapter_refusal`, `position_limit`,
    `RECURRENT_STATE`, `assignments_per_token` (expert assignments a
    token's forward makes; 0 without routed experts),
    `decode_span_tokens(block_size, max_blocks)` (the tokens one fold of
    the family's decode kernel covers, which `decode_spans_live` counts
    in) and `cache_counters`
    (what `stats()` reads out of the cache itself, fetched only then; {}
    where the cache holds no counter). `prefill` and `decode_step` are
    both handed the resolved `attention_impl`, which names every kernel
    of the family: a prefill with no kernel in it ignores it."""
    if name not in SERVING_FAMILIES:
        raise ValueError(
            f"unknown serving.model {name!r}; supported: "
            f"{', '.join(sorted(SERVING_FAMILIES))}")
    return importlib.import_module(SERVING_FAMILIES[name])


def family_of(cfg):
    """The serving module of `cfg`'s family, which the Config names."""
    return family_module(cfg.family)


def default_buckets(max_seq: int) -> List[int]:
    out = [b for b in DEFAULT_BUCKETS if b < max_seq]
    return out + [max_seq]


def load_checkpoint_params(
    checkpoint_ctx, storage_id: str, trial_id: Optional[int] = None
) -> Dict[str, Any]:
    """Verified params from a COMPLETED checkpoint (lineage fallback).

    `checkpoint_ctx` is a core CheckpointContext; `storage_id` may be
    "latest" (newest COMPLETED in the lineage). Integrity verification
    happens before restore; a corrupt candidate falls back through the
    COMPLETED lineage — serving a half-written model would be strictly
    worse than refusing to start.
    """
    from determined_tpu.core import CorruptCheckpoint

    candidates: List[str]
    if storage_id == "latest":
        candidates = checkpoint_ctx.lineage()
        if not candidates:
            raise FileNotFoundError(
                "serving.checkpoint=latest but the lineage has no "
                "COMPLETED checkpoint")
    else:
        candidates = [storage_id]
    last_err: Optional[Exception] = None
    for i, sid in enumerate(candidates):
        try:
            checkpoint_ctx.verify(sid)
            state = _restore_raw(checkpoint_ctx, sid)
            params = state.get("params") if isinstance(state, dict) else None
            if params is None:
                raise ValueError(
                    f"checkpoint {sid} has no 'params' subtree — not a "
                    "TrainState checkpoint")
            logger.info("serving params restored from checkpoint %s", sid)
            return params
        except (FileNotFoundError, CorruptCheckpoint) as e:
            last_err = e
            logger.warning("checkpoint %s unusable (%s); %s", sid, e,
                           "walking lineage back" if i + 1 < len(candidates)
                           else "lineage exhausted")
            if storage_id != "latest" and i == 0:
                # Explicit id failed: extend with the lineage behind it.
                candidates.extend(
                    c for c in checkpoint_ctx.lineage() if c != sid)
    raise last_err if last_err is not None else FileNotFoundError(storage_id)


def _restore_raw(checkpoint_ctx, storage_id: str) -> Any:
    """Whole-tree restore without a template (serving has no optimizer, so
    it cannot reconstruct the TrainState template the trainer restores
    into; orbax rebuilds the saved structure from checkpoint metadata).

    Leaves come back as host numpy arrays: a template-less device restore
    re-creates the SAVED shardings, so a checkpoint trained over four
    chips would refuse to load on a one-chip replica ("available devices
    are different") and, on a four-chip host, would hand the one-device
    engine params spread over all four."""
    import os

    import jax
    import orbax.checkpoint as ocp

    path = checkpoint_ctx._array_path(storage_id)
    state_dir = path + "/state" if "://" in path else os.path.join(
        path, "state")
    ckptr = ocp.PyTreeCheckpointer()
    as_numpy = jax.tree_util.tree_map(
        lambda _: ocp.RestoreArgs(restore_type=np.ndarray),
        ckptr.metadata(state_dir).item_metadata.tree)
    return ckptr.restore(state_dir, restore_args=as_numpy)


def _tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def resolve_attention_impl(impl: str, cfg) -> str:
    """serving.attention_impl → the engine's concrete path.

    "auto" picks the Pallas kernels on TPU and the jnp references
    elsewhere; "pallas"/"reference" force a path —
    a forced "pallas" off-TPU compiles only under a test's
    `pltpu.force_tpu_interpret_mode()`. A geometry one of the family's
    kernels cannot take (its `kernel_refusal`: the paged decode kernel's,
    and for a family with recurrent state the state kernel's) sends "auto"
    to the reference, said in the log, and makes an explicit "pallas"
    raise: the name is "pallas" only when every kernel is."""
    from determined_tpu.parallel.mesh import on_tpu

    why_not = family_of(cfg).kernel_refusal(cfg)
    if impl == "auto":
        if not on_tpu():
            return "reference"
        if why_not:
            logger.warning(
                "serving.attention_impl auto: reference path on a TPU (%s)",
                why_not)
            return "reference"
        return "pallas"
    if impl == "pallas" and why_not:
        raise ValueError(
            f"serving.attention_impl: pallas cannot serve this model: "
            f"{why_not}; use auto (which takes the reference path for "
            "it) or reference")
    if impl in ("pallas", "reference"):
        return impl
    raise ValueError(
        f"unknown serving.attention_impl {impl!r}; "
        "valid: auto, pallas, reference")


class ServingEngine:
    """Compiled prefill/decode over a fixed slot batch + KV cache.

    The cache is paged (docs/serving.md "Paged KV & prefix caching"): a
    block pool `[L, num_blocks + 1, block_size, Hkv*Dh]` (the extra block
    is the trash block for padded/inactive writes) plus per-slot block
    tables the batcher hands in at prefill. Every executable takes the
    table as an input canonicalized to the full
    `max_seq_len // block_size` length, so ONE decode executable and one
    prefill executable per token bucket cover every table — joining,
    retiring and prefix sharing never recompile. A family with recurrent
    state (docs/serving.md "Families with recurrent state") keeps a
    second pool in the same pytree, indexed by lane: prefill is told the
    lane, and nothing of that cache can be shared or copied.
    """

    def __init__(
        self,
        params: Dict[str, Any],
        cfg,
        *,
        slots: int = 8,
        max_seq_len: int = 256,
        prefill_buckets: Optional[Sequence[int]] = None,
        rules: Optional[LogicalRules] = None,
        seed: int = 0,
        attention_impl: str = "auto",
        kv_block_size: int = 16,
        kv_num_blocks: Optional[int] = None,
        adapters: Optional[Dict[str, Any]] = None,
    ):
        import jax
        import jax.numpy as jnp

        if slots <= 0:
            raise ValueError("slots must be positive")
        self.cfg = cfg
        self.family = fam = family_of(cfg)
        self.slots = slots
        limit = fam.position_limit(cfg)   # a position table, where any
        self.max_seq_len = min(max_seq_len, limit) if limit else max_seq_len
        buckets = sorted(set(
            min(b, self.max_seq_len)
            for b in (prefill_buckets or default_buckets(self.max_seq_len))))
        self.prefill_buckets = buckets
        self.rules = rules or LogicalRules()
        # One replica, one device: everything the executables take lives
        # on it (params may arrive as numpy or sharded over a mesh).
        device = jax.local_devices()[0]
        placed = jax.device_put(params, device)
        # Compute-ready once, here, and not again in every call
        # (the family's `resident_params`): one jitted cast where a leaf is wider
        # than the serving dtype, else the tree as placed. Nothing is
        # donated: the caller keeps what it handed in, and self.params is
        # the only copy the engine holds.
        def narrow(p):
            return fam.resident_params(p, cfg)

        self.weights_hbm_bytes = _tree_bytes(jax.eval_shape(narrow, placed))
        self.weights_narrowed_bytes = (
            _tree_bytes(placed) - self.weights_hbm_bytes)
        self.params = (jax.jit(narrow)(placed)
                       if self.weights_narrowed_bytes else placed)
        del placed
        # Multi-adapter serving (docs/serving.md "Model lifecycle"):
        # adapter name → params tree of a head-tuned fine-tune. Only the
        # (tied) embedding/LM-head table participates: the stack
        # [A+1, V, D] (index 0 = base) rides every compiled call and a
        # per-slot index selects each lane's table — one executable, one
        # KV pool, N fine-tunes. The transformer body stays the base's;
        # an adapter checkpoint whose body drifted from the base would
        # serve the base body silently, so we refuse anything but an
        # exact wte-shape match and document the contract.
        self.adapter_ids: Dict[str, int] = {"base": 0}
        self._adapter_stack = None
        self._slot_adapters = None
        why_not = fam.adapter_refusal(cfg) if adapters else None
        if why_not:
            raise ValueError(f"serving.adapters: {why_not}")
        if adapters:
            base_wte = self.params["wte"]
            tables = [base_wte]
            for name, tree in adapters.items():
                wte = tree.get("wte") if isinstance(tree, dict) else None
                if wte is None:
                    raise ValueError(
                        f"adapter {name!r}: checkpoint has no 'wte' table")
                if tuple(wte.shape) != tuple(base_wte.shape):
                    raise ValueError(
                        f"adapter {name!r}: wte shape {tuple(wte.shape)} "
                        f"!= base {tuple(base_wte.shape)} — adapters must "
                        "share the base model's geometry")
                self.adapter_ids[name] = len(tables)
                tables.append(jax.device_put(
                    jnp.asarray(wte, base_wte.dtype), device))
            self._adapter_stack = jnp.stack(tables)
            self._slot_adapters = np.zeros((slots,), np.int32)
        self.attention_impl = resolve_attention_impl(attention_impl, cfg)
        self.block_size = int(kv_block_size)
        self.num_blocks = int(kv_num_blocks) if kv_num_blocks else 0
        self._check_geometry()
        self._cache = None  # materialized at compile() (geometry may move)
        self._tables = None  # host [slots, max_blocks] int32
        self._rng = jax.random.PRNGKey(seed)
        self._step_counter = 0
        self._compiled_decode = None
        self._compiled_prefill: Dict[int, Any] = {}
        self._compiled_sample = None
        self._compiled_copy_block = None
        self.compile_stats: Dict[str, float] = {
            "weights_hbm_bytes": self.weights_hbm_bytes,
            "weights_narrowed_bytes": self.weights_narrowed_bytes,
        }
        # Warm-AOT provenance (docs/serving.md "Scale to zero"): how this
        # engine got its executables — "deserialize" when every piece came
        # from the compile-farm artifact store (a scale-from-zero cold
        # start that never re-traced), "mixed" for a partial hit, "trace"
        # for a cold compile.
        self.aot_source = "trace"
        # device-call counters (drained into /v1/stats)
        self.decode_steps = 0
        self.prefills = 0
        self.block_copies = 0
        # Bytes that crossed between host and device to sample first
        # tokens: the prefill call samples its own, so 4 a prefill.
        self.first_token_host_bytes = 0
        # What the decode kernel walks, per layer, summed over decode
        # calls (ops/paged_attention.py: a lane's 128-token spans up to
        # its position; an idle lane none), and the lanes whose recurrent
        # state the state kernel moves (ops/ssm_state.py: the live ones).
        self.decode_spans = 0
        self.state_lanes = 0
        # Prompt tokens prefills found cached (their K/V or latents were
        # not recomputed) and tokens they ran, summed over prefills.
        self.prefix_hit_tokens = 0
        self.prefix_novel_tokens = 0
        # Expert assignments made (a family with routed experts: tokens
        # run x experts per token x expert layers), host arithmetic.
        self._assignments_per_token = fam.assignments_per_token(cfg)
        self.moe_assignments = 0
        self._cache_counters: Dict[str, Any] = {}

    # -- paged geometry ------------------------------------------------

    def _check_geometry(self) -> None:
        if self.max_seq_len % self.block_size != 0:
            raise ValueError(
                f"kv_block_size {self.block_size} must divide max_seq_len "
                f"{self.max_seq_len} (preflight rule DTL206)")
        if not self.num_blocks:
            self.num_blocks = self.slots * (
                self.max_seq_len // self.block_size)
        # A pool smaller than one max_seq sequence is legal here (tests
        # build tiny backpressure pools); configs are gated by DTL206,
        # and the batcher rejects any request the pool can never cover.

    # -- adapters ------------------------------------------------------

    @property
    def has_adapters(self) -> bool:
        return self._adapter_stack is not None

    @property
    def adapter_names(self) -> List[str]:
        return [n for n in self.adapter_ids if n != "base"]

    def adapter_index(self, name: Optional[str]) -> int:
        """Stack index for a per-request `model:` name; '' / None /
        'base' = the base checkpoint. Unknown names raise ValueError —
        the HTTP front-end turns that into a 400, never a silent
        base-model answer the caller did not ask for."""
        if not name or name == "base":
            return 0
        idx = self.adapter_ids.get(name)
        if idx is None:
            raise ValueError(
                f"unknown adapter {name!r}; resident: "
                f"{self.adapter_names or '(none)'}")
        return idx

    def set_slot_adapter(self, slot: int, adapter: int) -> None:
        if self._slot_adapters is not None:
            self._slot_adapters[slot] = adapter

    @property
    def max_blocks_per_seq(self) -> int:
        return self.max_seq_len // self.block_size

    @property
    def trash_block(self) -> int:
        """Pool index of the write sink for padded/inactive lanes."""
        return self.num_blocks

    def set_block_geometry(self, block_size: int,
                           num_blocks: int) -> None:
        """Sync the device pool to an external BlockManager's geometry
        (the batcher calls this before compile so the tables it hands
        out index the real pool)."""
        if (self._compiled_decode is not None
                and (block_size != self.block_size
                     or num_blocks != self.num_blocks)):
            raise RuntimeError(
                "engine already compiled with block geometry "
                f"{self.num_blocks}x{self.block_size}; cannot switch to "
                f"{num_blocks}x{block_size}")
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self._check_geometry()

    def cache_hbm_bytes(self) -> int:
        """HBM the cache occupies, every pool of it (the admission
        budget's anchor)."""
        return self.family.cache_bytes(
            self.cfg, self.num_blocks + 1, self.block_size, self.slots)

    def cache_avals(self) -> Dict[str, Any]:
        """Shape and dtype of every pool of the cache, nothing allocated
        (the AOT signature keys them: serve/task.py serving_signature)."""
        import jax

        return jax.eval_shape(lambda: self.family.init_cache(
            self.cfg, self.num_blocks + 1, self.block_size, self.slots))

    def state_hbm_bytes(self) -> int:
        """The part of it a lane owns whatever its context: the recurrent
        state pool (0 for a family that leaves only K/V)."""
        return self.family.state_bytes(self.cfg, self.slots)

    # -- compilation ---------------------------------------------------

    def compile(self, farm=None) -> Dict[str, float]:
        """AOT-compile decode + every prefill bucket + the sampler.

        Runs before the HTTP front-end admits anything, so request latency
        never includes a trace/compile (and a config the model can't
        compile fails the replica at startup, not mid-traffic).

        With a `farm` (compile.runtime.FarmClient scoped to the serving
        signature), each executable is first warm-loaded from the PR-9
        artifact store — node-local AOT dir, then master — and only
        compiled when no artifact exists; fresh compiles are saved back
        (locally and uploaded) so the NEXT cold start deserializes in
        tens of milliseconds instead of tracing. This is what makes a
        scale-from-zero respawn fit inside cold_start_budget_s.
        """
        import jax

        from determined_tpu.compile import runtime as _crt

        fresh_artifacts: Dict[str, bytes] = {}
        hits = misses = 0

        def acquire(key, build):
            """Farm-load executable `key` or compile it fresh (queuing the
            serialized result for save-back). Farm failures degrade to the
            plain compile — the farm is an accelerator, not a dependency."""
            nonlocal hits, misses
            if farm is not None:
                loaded = farm.load_executable(key)
                if loaded is not None:
                    hits += 1
                    self.compile_stats[f"{key}_source"] = "deserialize"
                    return loaded
            compiled = build()
            misses += 1
            if farm is not None:
                self.compile_stats[f"{key}_source"] = "trace"
                try:
                    fresh_artifacts[_crt.aot_artifact_name(key)] = \
                        _crt.serialize_compiled(compiled)
                except Exception:
                    logger.debug("serve AOT serialize failed for %s", key,
                                 exc_info=True)
            return compiled

        t_all = time.monotonic()
        cfg, rules, fam = self.cfg, self.rules, self.family
        if self._cache is None:
            self._cache = fam.init_cache(
                cfg, self.num_blocks + 1, self.block_size, self.slots)
            self._tables = np.full(
                (self.slots, self.max_blocks_per_seq),
                self.trash_block, np.int32)
        sds = jax.ShapeDtypeStruct
        cache_sd = jax.tree_util.tree_map(
            lambda x: sds(x.shape, x.dtype), self._cache)
        params_sd = jax.tree_util.tree_map(
            lambda x: sds(x.shape, x.dtype), self.params)
        i32, f32 = np.int32, np.float32
        mb = self.max_blocks_per_seq
        impl = self.attention_impl

        # What a call takes beside (params, cache, ...): operands, never
        # executables. Multi-adapter replicas hand every decode / prefill
        # the [A+1, V, D] table stack and the per-lane index, so N
        # fine-tunes share one compile; a family with recurrent state
        # tells prefill which lane's state it writes.
        decode_extra: Dict[str, Any] = {}
        prefill_extra: Dict[str, Any] = {}
        if self.has_adapters:
            stack_sd = sds(self._adapter_stack.shape,
                           self._adapter_stack.dtype)
            decode_extra = {"adapters": stack_sd,
                            "slot_adapters": sds((self.slots,), i32)}
            prefill_extra = {"adapters": stack_sd,
                             "slot_adapter": sds((), i32)}
        if fam.RECURRENT_STATE:
            prefill_extra = {"slot": sds((), i32)}

        t0 = time.monotonic()

        def build_decode():
            decode = jax.jit(
                lambda p, c, t, pos, tbl, *extra: fam.decode_step(
                    p, c, t, pos, tbl, cfg, rules, attention_impl=impl,
                    **dict(zip(decode_extra, extra))),
                donate_argnums=(1,))
            return decode.lower(
                params_sd, cache_sd, sds((self.slots,), i32),
                sds((self.slots,), i32), sds((self.slots, mb), i32),
                *decode_extra.values()).compile()
        self._compiled_decode = acquire("decode", build_decode)
        self.compile_stats["decode_s"] = round(time.monotonic() - t0, 3)

        def prefill_and_first(p, c, t, ln, pfx, tbl, temp, key, step,
                              *extra):
            """The family's prefill and, in the same call, its sampler
            over the one row it produced: the token is on the device
            before the host could have fetched the logits. Temperature,
            base key and step counter are operands (the key is folded
            here, as `_next_rng` folds it for decode), so a bucket is one
            executable. The logits stay an output, left on the device."""
            cache, logits = fam.prefill(
                p, c, t, ln, pfx, tbl, cfg, rules, attention_impl=impl,
                **dict(zip(prefill_extra, extra)))
            first = fam.sample(logits[None], temp[None],
                               jax.random.fold_in(key, step))[0]
            return cache, first, logits

        def build_prefill(bucket):
            pf = jax.jit(prefill_and_first, donate_argnums=(1,))
            return pf.lower(
                params_sd, cache_sd, sds((bucket,), i32),
                sds((), i32), sds((), i32), sds((mb,), i32),
                sds((), f32), sds((2,), np.uint32), sds((), i32),
                *prefill_extra.values()).compile()

        for bucket in self.prefill_buckets:
            t0 = time.monotonic()
            # Keyed by the call's contract as well as its bucket (the
            # serving signature keys shapes, not outputs): an artifact of
            # a build whose prefill returned logits alone never loads here.
            self._compiled_prefill[bucket] = acquire(
                f"prefill_tok_{bucket}", lambda: build_prefill(bucket))
            self.compile_stats[f"prefill_{bucket}_s"] = round(
                time.monotonic() - t0, 3)

        if fam.copy_block is not None:
            t0 = time.monotonic()

            def build_copy():
                cp = jax.jit(fam.copy_block, donate_argnums=(0,))
                return cp.lower(
                    cache_sd, sds((), i32), sds((), i32)).compile()
            self._compiled_copy_block = acquire("copy_block", build_copy)
            self.compile_stats["copy_block_s"] = round(
                time.monotonic() - t0, 3)

        t0 = time.monotonic()

        def build_sample():
            sample = jax.jit(fam.sample)
            return sample.lower(
                sds((self.slots, cfg.vocab_size), f32),
                sds((self.slots,), f32),
                sds((2,), np.uint32)).compile()
        self._compiled_sample = acquire("sample", build_sample)
        self.compile_stats["sample_s"] = round(time.monotonic() - t0, 3)
        self.compile_stats["total_s"] = round(time.monotonic() - t_all, 3)
        if hits > 0:
            self.aot_source = "deserialize" if misses == 0 else "mixed"
        else:
            self.aot_source = "trace"
        self.compile_stats["aot_hits"] = hits
        self.compile_stats["aot_misses"] = misses
        if farm is not None and fresh_artifacts:
            # Save-back off the serving path: node-local first (the next
            # respawn on this node needs no master), then the farm store.
            farm.save_local(fresh_artifacts)
            farm.upload_async(
                fresh_artifacts,
                compile_ms=self.compile_stats["total_s"] * 1e3)
        logger.info("serving engine compiled (%s): %s", self.aot_source,
                    self.compile_stats)
        return dict(self.compile_stats)

    def bucket_for(self, length: int) -> Optional[int]:
        """Smallest compiled prefill bucket covering `length`; None when
        the prompt exceeds every bucket (reject at admission)."""
        for b in self.prefill_buckets:
            if length <= b:
                return b
        return None

    # -- device calls (batcher thread only) ----------------------------

    def _next_rng(self):
        import jax

        self._step_counter += 1
        return jax.random.fold_in(self._rng, self._step_counter)

    def _default_table(self, slot: int, n_blocks: int) -> list:
        """Static per-slot partition for direct engine use (no external
        BlockManager): slot i owns pool blocks [i*mb, (i+1)*mb)."""
        mb = self.max_blocks_per_seq
        if (slot + 1) * mb > self.num_blocks:
            raise ValueError(
                f"pool of {self.num_blocks} blocks cannot statically "
                f"partition slot {slot}; pass an explicit block_table")
        return list(range(slot * mb, slot * mb + n_blocks))

    def copy_block(self, src: int, dst: int) -> None:
        """Copy-on-write device copy: pool block `src` → `dst` across all
        layers (every pool the table addresses). The BlockManager decides
        WHEN (a shared block is about to be written); this mirrors it
        on-device."""
        if self.family.copy_block is None:
            raise ValueError(
                "copy_block: a recurrent state is the lane's own and no "
                "block of this family's cache is shared, so there is "
                "nothing to copy on write")
        if self._compiled_decode is None:
            self.compile()
        self._cache = self._compiled_copy_block(
            self._cache, np.int32(dst), np.int32(src))
        self.block_copies += 1

    def prefill_request(self, slot: int, tokens: np.ndarray,
                        temperature: float = 0.0,
                        block_table: Optional[Sequence[int]] = None,
                        cached_len: int = 0, adapter: int = 0,
                        request_id: Optional[str] = None) -> int:
        """Prefill `tokens` into the slot's cache; returns the first
        generated token. Compiled-bucket dispatch by NOVEL length: with
        `cached_len > 0` (prefix-cache hit) only the suffix
        `tokens[cached_len:]` runs through the model — the bucket, and
        therefore the prefill cost, shrinks to the novel part. `adapter`
        selects the slot's table from the adapter stack (0 = base); the
        slot keeps it for every decode step until release. `request_id`
        only labels the `serve.admit.prefill` phase."""
        if self._compiled_decode is None:
            self.compile()
        with trace.phase("serve.admit.prefill", slot=slot,
                         request=request_id) as ph:
            first, _ = self._enqueue_prefill(
                ph, slot, tokens, temperature, block_table, cached_len,
                adapter)
        return self._sample_first(first, temperature)

    def _enqueue_prefill(self, ph, slot: int, tokens: np.ndarray,
                         temperature: float,
                         block_table: Optional[Sequence[int]],
                         cached_len: int, adapter: int):
        """Host work of a prefill up to and with its enqueue → the first
        token, sampled in that call, and the logits it was sampled from,
        both still on the device (the serving path fetches the token
        alone)."""
        if adapter and not self.has_adapters:
            raise ValueError("engine has no adapters resident")
        self.set_slot_adapter(slot, adapter)
        length = int(tokens.shape[0])
        if cached_len and self.family.RECURRENT_STATE:
            raise ValueError(
                f"cached_len {cached_len}: a recurrent state cannot be "
                "rebuilt from shared prefix blocks, so this family "
                "prefills every prompt whole (serving.prefix_cache: false)")
        if not 0 <= cached_len < length:
            raise ValueError(
                f"cached_len {cached_len} must leave >= 1 novel token "
                f"of the {length}-token prompt")
        mb = self.max_blocks_per_seq
        if block_table is None:
            # Direct engine use (no BlockManager): the slot's whole
            # static partition, so decode can grow past the prompt.
            block_table = self._default_table(slot, mb)
        table = np.full((mb,), self.trash_block, np.int32)
        table[:min(len(block_table), mb)] = list(block_table)[:mb]
        suffix = np.asarray(tokens, np.int32)[cached_len:]
        s_len = int(suffix.shape[0])
        bucket = self.bucket_for(s_len)
        if bucket is None:
            raise ValueError(
                f"suffix length {s_len} exceeds the largest prefill "
                f"bucket ({self.prefill_buckets[-1]})")
        padded = np.zeros((bucket,), np.int32)
        padded[:s_len] = suffix
        self._step_counter += 1      # this call's fold of the key
        args = [self.params, self._cache, padded,
                np.int32(s_len), np.int32(cached_len), table,
                np.float32(temperature), self._rng,
                np.int32(self._step_counter)]
        if self.has_adapters:
            args += [self._adapter_stack, np.int32(adapter)]
        if self.family.RECURRENT_STATE:
            args.append(np.int32(slot))
        ph.set(bucket=bucket, novel=s_len)
        self._cache, first, logits = self._compiled_prefill[bucket](*args)
        self._tables[slot] = table
        self.prefills += 1
        self.prefix_hit_tokens += cached_len
        self.prefix_novel_tokens += s_len
        self.moe_assignments += s_len * self._assignments_per_token
        return first, logits

    def _sample_first(self, first, temperature: float) -> int:
        """Fetch the token the prefill call sampled (with `temperature`,
        which went in as its operand): four bytes, and where the host
        waits for the prefill."""
        del temperature
        with trace.phase("serve.admit.first_token"):
            token = np.asarray(first)
            self.first_token_host_bytes += token.nbytes
            return int(token)

    def release_slot(self, slot: int) -> None:
        """Point a retired slot's table at the trash block so later
        decode steps can never touch its (possibly reallocated) blocks,
        and hand the lane's adapter back to base."""
        if self._tables is not None:
            self._tables[slot] = self.trash_block
        self.set_slot_adapter(slot, 0)

    def decode(self, tokens: np.ndarray, positions: np.ndarray,
               temperatures: np.ndarray) -> np.ndarray:
        """One decode step for all slots → sampled next tokens [slots].

        Feeds the per-slot block tables recorded at prefill (they only
        change at admission/CoW, both of which happen at step
        boundaries in the batcher thread)."""
        if self._compiled_decode is None:
            self.compile()
        with trace.phase("serve.step.dispatch"):
            args = [self.params, self._cache, np.asarray(tokens, np.int32),
                    np.asarray(positions, np.int32), self._tables]
            if self.has_adapters:
                args += [self._adapter_stack, self._slot_adapters.copy()]
            self._cache, logits = self._compiled_decode(*args)
            toks = self._compiled_sample(
                logits, np.asarray(temperatures, np.float32),
                self._next_rng())
            self.decode_steps += 1
            live = self._tables[:, 0] != self.trash_block
            self.decode_spans += live_spans(
                positions, live, self.family.decode_span_tokens(
                    self.block_size, self.max_blocks_per_seq))
            n_live = live_lanes(live)
            if self.family.RECURRENT_STATE:
                self.state_lanes += n_live
            self.moe_assignments += n_live * self._assignments_per_token
        with trace.phase("serve.step.fetch"):
            return np.asarray(toks)

    def stats(self) -> Dict[str, Any]:
        if self._cache is not None:
            # Read out of the cache itself, here and in no step: for a
            # family that keeps a counter there this is a device fetch,
            # made on the caller's thread (the HTTP thread for /metrics
            # and /v1/stats). A read that lost the race with a call the
            # batcher donated the cache to returns None: keep the last.
            self._cache_counters = self.family.cache_counters(
                self.cfg, self._cache, self.num_blocks + 1,
                self.block_size) or self._cache_counters
        return {
            **self._cache_counters,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_novel_tokens": self.prefix_novel_tokens,
            "moe_assignments": self.moe_assignments,
            "slots": self.slots,
            "adapters": self.adapter_names,
            "max_seq_len": self.max_seq_len,
            "prefill_buckets": list(self.prefill_buckets),
            "attention_impl": self.attention_impl,
            "kv_layout": "paged",
            "kv_block_size": self.block_size,
            "kv_num_blocks": self.num_blocks,
            "cache_hbm_bytes": self.cache_hbm_bytes(),
            "state_hbm_bytes": self.state_hbm_bytes(),
            "weights_hbm_bytes": self.weights_hbm_bytes,
            "weights_narrowed_bytes": self.weights_narrowed_bytes,
            "decode_steps": self.decode_steps,
            "prefills": self.prefills,
            "block_copies": self.block_copies,
            "first_token_host_bytes": self.first_token_host_bytes,
            # Spans that held a visible key, and spans the kernel was
            # launched over: its grid is over lanes and its loop ends at
            # the position, so one count is both.
            "decode_spans_live": self.decode_spans,
            "decode_spans_grid": self.decode_spans,
            # Lanes whose recurrent state a decode call held live, and
            # lanes whose state the kernel moved, per layer: its programs
            # stand on live lanes only, so one count is both.
            "state_lanes_live": self.state_lanes,
            "state_lanes_grid": self.state_lanes,
            "compile": dict(self.compile_stats),
        }
