"""expconf schema tests: searcher/storage/mesh validation and defaults.

Reference discipline: schemas/expconf/v0/*.json validation in the master's
pkg/schemas/expconf (SURVEY.md §5 "Config/flag system")."""

import pytest

from determined_tpu import expconf


def base_config(**over):
    c = {
        "entrypoint": "python3 train.py",
        "searcher": {
            "name": "single",
            "metric": "loss",
            "max_length": {"batches": 10},
        },
    }
    c.update(over)
    return c


class TestValidate:
    def test_valid_minimal(self):
        assert expconf.validate(base_config()) == []

    def test_missing_entrypoint(self):
        c = base_config()
        del c["entrypoint"]
        assert any("entrypoint" in e for e in expconf.validate(c))

    def test_azure_requires_container(self):
        c = base_config(checkpoint_storage={"type": "azure"})
        assert any("container" in e for e in expconf.validate(c))
        c = base_config(
            checkpoint_storage={"type": "azure", "container": "ckpts"}
        )
        assert expconf.validate(c) == []


class TestMeshValidation:
    """hyperparameters.mesh is the single validated home of the mesh config."""

    def test_valid_mesh(self):
        c = base_config(
            hyperparameters={"mesh": {"data": -1, "fsdp": 4}},
            resources={"slots_per_trial": 8},
        )
        assert expconf.validate(c) == []

    def test_unknown_axis_rejected(self):
        c = base_config(hyperparameters={"mesh": {"warp": 2}})
        errs = expconf.validate(c)
        assert any("unknown axes" in e and "warp" in e for e in errs)

    def test_two_minus_ones_rejected(self):
        c = base_config(hyperparameters={"mesh": {"data": -1, "fsdp": -1}})
        assert any("at most one axis may be -1" in e for e in expconf.validate(c))

    def test_zero_size_rejected(self):
        c = base_config(hyperparameters={"mesh": {"data": 0}})
        assert any("positive int or -1" in e for e in expconf.validate(c))

    def test_bool_size_rejected(self):
        # YAML `data: true` must not slip through as int(1)
        c = base_config(hyperparameters={"mesh": {"data": True}})
        assert any("positive int or -1" in e for e in expconf.validate(c))

    def test_product_must_match_slots(self):
        c = base_config(
            hyperparameters={"mesh": {"data": 2, "tensor": 3}},
            resources={"slots_per_trial": 8},
        )
        assert any("axis product 6" in e for e in expconf.validate(c))

    def test_mesh_without_resources_checks_default_slots(self):
        # apply_defaults sets slots_per_trial=1; a fixed 8-chip mesh with no
        # resources block must fail at submit, not at MeshConfig.resolve().
        c = base_config(hyperparameters={"mesh": {"data": 8}})
        assert any("axis product 8" in e for e in expconf.validate(c))

    def test_slots_divisibility_with_wildcard(self):
        c = base_config(
            hyperparameters={"mesh": {"data": -1, "tensor": 3}},
            resources={"slots_per_trial": 8},
        )
        assert any("not divisible" in e for e in expconf.validate(c))

    def test_check_raises_on_bad_mesh(self):
        c = base_config(hyperparameters={"mesh": {"bogus": 1}})
        with pytest.raises(ValueError, match="bogus"):
            expconf.check(c)


class TestOptimizationsBlock:
    """`optimizations:` — TPU training-perf knobs (docs/training-perf.md),
    validated at submit so a typo'd attention_impl fails before compile."""

    def test_valid_block(self):
        c = base_config(optimizations={
            "attention_impl": "pallas",
            "attention_bf16": True,
            "overlap_allgather": True,
            "prepartition_inputs": False,
        })
        assert expconf.validate(c) == []

    @pytest.mark.parametrize("impl", ["auto", "pallas", "reference", "dense"])
    def test_every_impl_accepted(self, impl):
        c = base_config(optimizations={"attention_impl": impl})
        assert expconf.validate(c) == []

    def test_bad_impl_rejected(self):
        c = base_config(optimizations={"attention_impl": "palas"})
        assert any("attention_impl" in e and "palas" in e
                   for e in expconf.validate(c))

    def test_unknown_key_rejected(self):
        c = base_config(optimizations={"attension_bf16": True})
        assert any("attension_bf16" in e for e in expconf.validate(c))

    def test_non_bool_flag_rejected(self):
        c = base_config(optimizations={"attention_bf16": "yes"})
        assert any("attention_bf16" in e for e in expconf.validate(c))

    def test_must_be_mapping(self):
        c = base_config(optimizations=["attention_impl"])
        assert any("optimizations" in e and "mapping" in e
                   for e in expconf.validate(c))

    def test_defaults_fill_block(self):
        out = expconf.apply_defaults(base_config())
        assert out["optimizations"] == {
            "attention_impl": "auto",
            "attention_bf16": False,
            "overlap_allgather": False,
            "prepartition_inputs": True,
        }

    def test_defaults_keep_explicit_values(self):
        out = expconf.apply_defaults(
            base_config(optimizations={"attention_impl": "dense"}))
        assert out["optimizations"]["attention_impl"] == "dense"
        assert out["optimizations"]["prepartition_inputs"] is True


class TestDefaults:
    def test_no_dead_tpu_block(self):
        # The mesh config has exactly one home: hyperparameters.mesh.
        out = expconf.apply_defaults(base_config())
        assert "tpu" not in out

    def test_core_defaults(self):
        out = expconf.apply_defaults(base_config())
        assert out["max_restarts"] == 5
        assert out["resources"]["slots_per_trial"] == 1


class TestLegacyShims:
    """Version shims (reference pkg/schemas/expconf/legacy.go): old config
    shapes keep working through expconf.check()."""

    def _base(self, **searcher):
        return {
            "entrypoint": "python3 train.py",
            "searcher": {"name": "single", "metric": "loss", **searcher},
        }

    def test_bare_int_lengths(self):
        cfg = self._base(max_length=500)
        cfg["min_validation_period"] = 50
        out = expconf.check(cfg)
        assert out["searcher"]["max_length"] == {"batches": 500}
        assert out["min_validation_period"] == {"batches": 50}

    def test_max_steps_alias(self):
        out = expconf.check(self._base(max_steps=100))
        assert out["searcher"]["max_length"] == {"batches": 100}

    def test_resources_slots_alias(self):
        cfg = self._base(max_length={"batches": 4})
        cfg["resources"] = {"slots": 8}
        out = expconf.check(cfg)
        assert out["resources"]["slots_per_trial"] == 8

    def test_dropped_container_era_keys_warn(self):
        import warnings

        cfg = self._base(max_length={"batches": 4})
        cfg["bind_mounts"] = [{"host_path": "/x", "container_path": "/y"}]
        cfg["optimizations"] = {"aggregation_frequency": 2}
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            out = expconf.check(cfg)
        assert "bind_mounts" not in out
        # The torch-era key is shimmed away; the block itself survives as
        # the TPU optimizations knobs, filled with defaults.
        assert "aggregation_frequency" not in out["optimizations"]
        assert out["optimizations"]["attention_impl"] == "auto"
        joined = " ".join(str(x.message) for x in w)
        assert "bind_mounts" in joined and "aggregation_frequency" in joined

    def test_legacy_adaptive_runs_through(self):
        out = expconf.check({
            "entrypoint": "python3 train.py",
            "searcher": {"name": "adaptive", "metric": "loss",
                         "max_length": 16, "max_trials": 4},
        })
        assert out["searcher"]["max_length"] == {"batches": 16}
        assert out["searcher"]["divisor"] == 4


class TestPreflightBlock:
    """The `preflight:` config block (docs/preflight.md) is schema-checked
    like every other block."""

    def test_valid_block(self):
        c = base_config(preflight={"gate": "error",
                                   "suppress": ["DTL001", "DTL201"],
                                   "hbm_gb_per_device": 16})
        assert expconf.validate(c) == []

    def test_bad_gate(self):
        c = base_config(preflight={"gate": "maybe"})
        assert any("preflight.gate" in e for e in expconf.validate(c))

    def test_bad_suppress_code(self):
        c = base_config(preflight={"suppress": ["DTL1", 7]})
        errs = expconf.validate(c)
        assert sum("preflight.suppress" in e for e in errs) == 2

    def test_bad_hbm(self):
        c = base_config(preflight={"hbm_gb_per_device": -1})
        assert any("hbm_gb_per_device" in e for e in expconf.validate(c))


class TestPrefetchBlock:
    """The `prefetch:` config block (async input pipeline,
    docs/trial-api.md): on by default, opt-out + depth knobs."""

    def test_valid_block(self):
        c = base_config(prefetch={"enabled": True, "depth": 4,
                                  "shard": True})
        assert expconf.validate(c) == []

    def test_bare_bool(self):
        assert expconf.validate(base_config(prefetch=False)) == []

    def test_bad_depth(self):
        for depth in (0, -1, 1.5, True, "two"):
            c = base_config(prefetch={"depth": depth})
            assert any("prefetch.depth" in e for e in expconf.validate(c)), depth

    def test_bad_enabled(self):
        c = base_config(prefetch={"enabled": "yes"})
        assert any("prefetch.enabled" in e for e in expconf.validate(c))

    def test_unknown_key(self):
        c = base_config(prefetch={"buffers": 3})
        assert any("unknown keys" in e for e in expconf.validate(c))

    def test_defaults_applied(self):
        out = expconf.apply_defaults(base_config())
        assert out["prefetch"] == {"enabled": True, "depth": 2}

    def test_defaults_keep_user_values(self):
        out = expconf.apply_defaults(base_config(prefetch={"depth": 8}))
        assert out["prefetch"] == {"enabled": True, "depth": 8}


class TestHealthBlock:
    """The `health:` config block (self-healing loop,
    docs/checkpointing.md): divergence sentinel policy + step watchdog."""

    def test_valid_block(self):
        c = base_config(health={"on_nan": "rollback", "rollback_window": 4,
                                "max_rollbacks": 2, "step_timeout_sec": 120})
        assert expconf.validate(c) == []

    def test_bad_on_nan(self):
        c = base_config(health={"on_nan": "explode"})
        assert any("health.on_nan" in e for e in expconf.validate(c))

    def test_bad_window(self):
        for w in (-1, 1.5, True, "many"):
            c = base_config(health={"rollback_window": w})
            assert any("rollback_window" in e for e in expconf.validate(c)), w

    def test_zero_max_rollbacks_rejected(self):
        c = base_config(health={"max_rollbacks": 0})
        assert any("max_rollbacks" in e for e in expconf.validate(c))

    def test_bad_timeout(self):
        c = base_config(health={"step_timeout_sec": -5})
        assert any("step_timeout_sec" in e for e in expconf.validate(c))

    def test_unknown_key(self):
        c = base_config(health={"watchdog": True})
        assert any("unknown keys" in e for e in expconf.validate(c))

    def test_not_a_mapping(self):
        c = base_config(health=True)
        assert any("health must be a mapping" in e for e in expconf.validate(c))

    def test_defaults_applied(self):
        out = expconf.apply_defaults(base_config())
        assert out["health"] == {"on_nan": "warn", "rollback_window": 8,
                                 "max_rollbacks": 3, "step_timeout_sec": 0}

    def test_defaults_keep_user_values(self):
        out = expconf.apply_defaults(base_config(health={"on_nan": "fail"}))
        assert out["health"]["on_nan"] == "fail"
        assert out["health"]["step_timeout_sec"] == 0


class TestPreemptionBlock:
    """The `preemption:` config block (spot-survival emergency checkpoint,
    docs/checkpointing.md "Emergency checkpoints")."""

    def test_valid_block(self):
        c = base_config(preemption={"emergency_checkpoint": True,
                                    "budget_safety_factor": 2.0,
                                    "budget_margin_sec": 5})
        assert expconf.validate(c) == []

    def test_bare_bool_is_valid(self):
        assert expconf.validate(base_config(preemption=False)) == []

    def test_bad_emergency_checkpoint(self):
        c = base_config(preemption={"emergency_checkpoint": "yes"})
        assert any("emergency_checkpoint" in e for e in expconf.validate(c))

    def test_bad_safety_factor(self):
        for v in (0, 0.5, True, "fast"):
            c = base_config(preemption={"budget_safety_factor": v})
            assert any("budget_safety_factor" in e
                       for e in expconf.validate(c)), v

    def test_bad_margin(self):
        for v in (-1, True, "soon"):
            c = base_config(preemption={"budget_margin_sec": v})
            assert any("budget_margin_sec" in e
                       for e in expconf.validate(c)), v

    def test_unknown_key(self):
        c = base_config(preemption={"grace": 30})
        assert any("unknown keys" in e for e in expconf.validate(c))

    def test_not_a_mapping(self):
        c = base_config(preemption=[30])
        assert any("preemption must be a bool or a mapping" in e
                   for e in expconf.validate(c))

    def test_defaults_applied(self):
        out = expconf.apply_defaults(base_config())
        assert out["preemption"] == {"emergency_checkpoint": True,
                                     "budget_safety_factor": 1.5,
                                     "budget_margin_sec": 2.0}

    def test_defaults_keep_user_values(self):
        out = expconf.apply_defaults(
            base_config(preemption={"budget_margin_sec": 7}))
        assert out["preemption"]["budget_margin_sec"] == 7
        assert out["preemption"]["emergency_checkpoint"] is True


class TestServingBlock:
    """`serving:` — a det serve deployment config (docs/serving.md)."""

    def _config(self, **serving):
        return {
            "name": "serve-test",
            "serving": {"checkpoint": "trial0-step2", **serving},
            "checkpoint_storage": {"type": "shared_fs",
                                   "host_path": "/tmp/x"},
        }

    def test_minimal_serving_config_valid(self):
        # No entrypoint, no searcher: serving configs are deployments.
        assert expconf.validate(self._config()) == []

    def test_defaults_fill_capacity_knobs(self):
        c = expconf.check(self._config())
        s = c["serving"]
        assert s["max_batch_size"] == 8
        assert s["max_seq_len"] == 256
        assert s["kv_block_size"] == 16
        assert s["queue_depth"] == 64
        assert s["model"] == "gpt2"
        # and no searcher machinery was bolted on
        assert "searcher" not in c

    def test_unknown_keys_flagged(self):
        errs = expconf.validate(self._config(batch_sise=4))
        assert any("unknown keys" in e for e in errs)

    def test_bad_values_flagged(self):
        errs = expconf.validate(self._config(max_batch_size=0))
        assert any("max_batch_size" in e for e in errs)
        errs = expconf.validate(self._config(model="bert"))
        assert any("serving.model" in e for e in errs)
        errs = expconf.validate(self._config(prefill_buckets=[64, 32]))
        assert any("ascending" in e for e in errs)
        errs = expconf.validate(self._config(prefill_buckets=[]))
        assert any("prefill_buckets" in e for e in errs)

    def test_paged_kv_knobs_validate_and_default(self):
        # Defaults: paged layout with prefix caching on, impl auto.
        c = expconf.check(self._config())
        assert c["serving"]["prefix_cache"] is True
        assert c["serving"]["attention_impl"] == "auto"
        assert "kv_num_blocks" not in c["serving"]  # derived, not defaulted
        # Valid explicit values pass.
        assert expconf.validate(self._config(
            attention_impl="pallas", prefix_cache=False,
            kv_num_blocks=128)) == []
        # Bad values are rejected.
        errs = expconf.validate(self._config(attention_impl="flash"))
        assert any("attention_impl" in e for e in errs)
        errs = expconf.validate(self._config(prefix_cache="yes"))
        assert any("prefix_cache" in e for e in errs)
        errs = expconf.validate(self._config(kv_num_blocks=0))
        assert any("kv_num_blocks" in e for e in errs)

    def test_serving_attention_impl_dense_is_refused(self):
        """The slot-dense cache is deleted: its value is refused at
        validation, with the three that exist named."""
        errs = expconf.validate(self._config(attention_impl="dense"))
        assert [e for e in errs if "attention_impl" in e] == [
            "serving.attention_impl must be one of: auto, pallas, "
            "reference"]

    def test_serving_must_be_mapping(self):
        errs = expconf.validate({"name": "x", "serving": "yes"})
        assert any("serving must be a mapping" in e for e in errs)

    def test_trial_configs_still_require_searcher(self):
        errs = expconf.validate({"name": "x", "entrypoint": "python3 t.py"})
        assert any("searcher is required" in e for e in errs)

    # -- model lifecycle (docs/serving.md "Model lifecycle") ------------

    def test_serving_adapters_valid(self):
        cfg = self._config(adapters=[
            {"name": "ft-a", "checkpoint": "ck-a"},
            {"name": "ft-b", "checkpoint": "ck-b"},
        ])
        assert expconf.validate(cfg) == []

    @pytest.mark.parametrize("adapters,needle", [
        ("ft", "must be a list"),
        ([["x"]], "must be a mapping"),
        ([{"checkpoint": "ck"}], "name must be a non-empty string"),
        ([{"name": "", "checkpoint": "ck"}], "non-empty string"),
        ([{"name": "a", "checkpoint": "c1"},
          {"name": "a", "checkpoint": "c2"}], "duplicate"),
        ([{"name": "base", "checkpoint": "ck"}], "reserved"),
        ([{"name": "a"}], "checkpoint must be a checkpoint storage id"),
        ([{"name": "a", "checkpoint": "ck", "rank": 8}], "unknown keys"),
    ])
    def test_serving_adapters_invalid(self, adapters, needle):
        errs = expconf.validate(self._config(adapters=adapters))
        assert any(needle in e for e in errs), (adapters, errs)

    def test_serving_canary_valid_and_defaults(self):
        cfg = self._config(canary={"model": "m", "version": 2,
                                   "fraction": 0.1})
        assert expconf.validate(cfg) == []
        out = expconf.check(cfg)
        assert out["serving"]["canary"]["replicas"] == 1
        # fraction defaults to 0.05 when omitted
        out = expconf.check(self._config(canary={"checkpoint": "ck-2"}))
        assert out["serving"]["canary"]["fraction"] == 0.05

    @pytest.mark.parametrize("canary,needle", [
        ("v2", "must be a mapping"),
        ({"fraction": 0.1}, "requires `model`"),
        ({"model": "m", "fraction": 0}, "(0, 1)"),
        ({"model": "m", "fraction": 1}, "(0, 1)"),
        ({"model": "m", "fraction": True}, "(0, 1)"),
        ({"model": "m", "version": 0}, "positive int"),
        ({"checkpoint": "ck", "version": 2}, "requires `model`"),
        ({"model": "m", "replicas": 0}, "replicas must be a positive"),
        ({"model": "m", "surge": 1}, "unknown keys"),
    ])
    def test_serving_canary_invalid(self, canary, needle):
        errs = expconf.validate(self._config(canary=canary))
        assert any(needle in e for e in errs), (canary, errs)

    def test_serving_model_version_label(self):
        assert expconf.validate(self._config(model_version="m:3")) == []
        errs = expconf.validate(self._config(model_version=""))
        assert any("model_version" in e for e in errs)


class TestRegistryBlock:
    """`registry:` — train→serve auto-promotion (docs/serving.md
    'Model lifecycle')."""

    def _config(self, registry):
        return {
            "name": "t",
            "entrypoint": "python3 train.py",
            "searcher": {"name": "single", "metric": "loss",
                         "max_length": {"batches": 4}},
            "registry": registry,
        }

    def test_valid_and_promote_default(self):
        cfg = self._config({"model": "prod-gpt2"})
        assert expconf.validate(cfg) == []
        out = expconf.check(cfg)
        assert out["registry"]["promote"] == "best"
        assert expconf.validate(
            self._config({"model": "m", "promote": "latest"})) == []

    @pytest.mark.parametrize("registry,needle", [
        ("m", "registry must be a mapping"),
        ({}, "registry.model"),
        ({"model": ""}, "registry.model"),
        ({"model": 3}, "registry.model"),
        ({"model": "m:2"}, "bare model name"),
        ({"model": "m", "promote": "newest"}, "best, latest"),
        ({"model": "m", "version": 2}, "unknown keys"),
    ])
    def test_invalid(self, registry, needle):
        errs = expconf.validate(self._config(registry))
        assert any(needle in e for e in errs), (registry, errs)

    def test_registry_refused_on_serving_configs(self):
        cfg = {"name": "d", "serving": {"model": "gpt2"},
               "registry": {"model": "m"}}
        errs = expconf.validate(cfg)
        assert any("belongs to training configs" in e for e in errs)


class TestCrossFieldDiagnostics:
    """Cross-field checks surface as DTL rules (the same codes the native
    master enforces at experiment create), not bare exceptions."""

    def test_batch_mesh_divisible_clean(self):
        c = base_config(
            hyperparameters={"global_batch_size": 32},
            resources={"slots_per_trial": 8},
        )
        assert expconf.cross_field_diagnostics(c) == []

    def test_batch_mesh_mismatch_dtl201(self):
        c = base_config(
            hyperparameters={"global_batch_size": 30},
            resources={"slots_per_trial": 8},
        )
        diags = expconf.cross_field_diagnostics(c)
        assert [d.code for d in diags] == ["DTL201"]
        assert diags[0].level == "error"
        assert "30" in diags[0].message

    def test_explicit_mesh_batch_axes(self):
        # data=2 x fsdp=2 (tensor=2 is not a batch axis) -> product 4.
        c = base_config(
            hyperparameters={
                "global_batch_size": 6,
                "mesh": {"data": 2, "fsdp": 2, "tensor": 2},
            },
            resources={"slots_per_trial": 8},
        )
        assert [d.code for d in expconf.cross_field_diagnostics(c)] == [
            "DTL201"]
        c["hyperparameters"]["global_batch_size"] = 8
        assert expconf.cross_field_diagnostics(c) == []

    def test_const_hparam_spec_unwrapped(self):
        c = base_config(
            hyperparameters={
                "global_batch_size": {"type": "const", "val": 30}},
            resources={"slots_per_trial": 8},
        )
        assert [d.code for d in expconf.cross_field_diagnostics(c)] == [
            "DTL201"]

    def _asha(self, max_length, num_rungs=5, divisor=4):
        return base_config(searcher={
            "name": "async_halving", "metric": "loss",
            "max_length": {"batches": max_length},
            "num_rungs": num_rungs, "divisor": divisor,
        })

    def test_asha_budget_too_small_dtl202(self):
        diags = expconf.cross_field_diagnostics(self._asha(100))
        assert [d.code for d in diags] == ["DTL202"]
        assert diags[0].level == "error"

    def test_asha_budget_sufficient(self):
        assert expconf.cross_field_diagnostics(self._asha(256)) == []

    def test_asha_legacy_bare_int_length_shimmed(self):
        c = self._asha(100)
        c["searcher"]["max_length"] = 100  # legacy bare int
        assert [d.code for d in expconf.cross_field_diagnostics(c)] == [
            "DTL202"]


def test_all_shipped_example_configs_validate():
    """Every yaml under examples/ must pass expconf.check — shipped
    configs rotting against schema changes is exactly what the reference's
    schema CI prevents."""
    import glob
    import os

    import yaml

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    configs = sorted(glob.glob(os.path.join(repo, "examples", "*", "*.yaml")))
    assert len(configs) >= 8, configs
    for path in configs:
        with open(path) as f:
            cfg = yaml.safe_load(f)
        try:
            expconf.check(cfg)
        except ValueError as e:
            raise AssertionError(f"{path} fails validation: {e}") from None
