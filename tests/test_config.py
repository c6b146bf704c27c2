"""Unit tests for the config system (SURVEY.md §6 config/flag system)."""

import pytest

from orion_tpu.config import (
    Config,
    ParallelConfig,
    apply_overrides,
    get_config,
    list_presets,
)


def test_presets_cover_baseline_workloads():
    # The five BASELINE.json workloads must all have presets.
    names = list_presets()
    for required in (
        "gpt2-125m",
        "llama3-8b-dp",
        "llama3-70b-fsdp",
        "mixtral-8x7b-ep",
        "llama3-8b-infer",
    ):
        assert required in names


def test_overrides_typed():
    cfg = get_config("tiny", ["model.n_layers=3", "data.batch_size=2",
                              "optimizer.learning_rate=1e-3",
                              "model.tie_embeddings=false"])
    assert cfg.model.n_layers == 3
    assert cfg.data.batch_size == 2
    assert cfg.optimizer.learning_rate == pytest.approx(1e-3)
    assert cfg.model.tie_embeddings is False


def test_overrides_optional_and_tuple_types():
    # Regression: `from __future__ import annotations` stringifies field types;
    # overrides must still resolve Optional[int] / Tuple[...] correctly.
    cfg = apply_overrides(Config(), [
        "model.head_dim=64",
        "optimizer.decay_steps=2000",
        "train.profile_steps=10,20",
        "parallel.dcn_axes=dp",
        "model.head_dim=none",
    ])
    assert cfg.model.head_dim is None
    assert cfg.optimizer.decay_steps == 2000
    assert cfg.train.profile_steps == (10, 20)
    assert cfg.parallel.dcn_axes == ("dp",)


# A name that never was a field, and the six PR 49 removed (nothing set
# them): a stale script must not silently carry a dead option.
@pytest.mark.parametrize("key", [
    "model.not_a_field",
    "model.attn_block_q",
    "model.attn_block_kv",
    "train.peak_flops_per_device",
    "inference.decode_window_autotune",
    "inference.decode_window_max",
    "inference.decode_host_share_target",
])
def test_override_unknown_key_raises(key):
    name = key.split(".")[1]
    with pytest.raises(ValueError, match=f"unknown config key '{name}'"):
        apply_overrides(Config(), [f"{key}=1"])


def test_parallel_num_devices():
    p = ParallelConfig(dp=2, fsdp=2, tp=2)
    assert p.num_devices == 8


def test_param_count_sane():
    gpt2 = get_config("gpt2-125m").model
    # GPT-2 125M: ~124M params (with the padded 50304 vocab).
    n = gpt2.num_params()
    assert 100e6 < n < 180e6

    llama = get_config("llama3-8b-dp").model
    n = llama.num_params()
    assert 7e9 < n < 9e9

    llama70 = get_config("llama3-70b-fsdp").model
    assert 65e9 < llama70.num_params() < 75e9


def test_moe_flops_use_active_experts_only():
    mix = get_config("mixtral-8x7b-ep").model
    dense_equiv = mix.flops_per_token()
    # Active params ~13B of 47B total: flops must be well under total-param flops.
    assert dense_equiv < 6 * mix.num_params()


def test_config_json_roundtrip():
    cfg = get_config("tiny")
    s = cfg.to_json()
    assert '"n_layers": 2' in s


def test_tuple_override_forms():
    """Tuple overrides accept python-repr, bare, and json forms; elements
    are typed (the '(5,7)' form previously parsed to ('(5', '7)') strings,
    silently disabling train.profile_steps)."""
    from orion_tpu.config import get_config

    for ov, want in [
        ("train.profile_steps=(5,7)", (5, 7)),
        ("train.profile_steps=5,7", (5, 7)),
        ("train.profile_steps=[5,7]", (5, 7)),
        ("train.profile_steps=none", None),
    ]:
        assert get_config("tiny", [ov]).train.profile_steps == want, ov
    for ov, want in [
        ('parallel.dcn_axes=("dp",)', ("dp",)),
        ("parallel.dcn_axes=dp", ("dp",)),
        ("parallel.dcn_axes=dp,fsdp", ("dp", "fsdp")),
    ]:
        assert get_config("tiny", [ov]).parallel.dcn_axes == want, ov


def test_leaf_configs_validate_and_overrides_batch_per_section():
    """ISSUE 15: every leaf *Config validates in __post_init__, and
    same-section overrides apply as ONE replace — cross-field checks
    (memmap-requires-path) hold in either flag order."""
    import pytest

    from orion_tpu.config import (
        DataConfig, OptimizerConfig, RuntimeConfig, get_config,
    )

    # Cross-field check is order-independent under the override parser.
    for order in (
        ["data.source=memmap", "data.path=/tmp/x.bin"],
        ["data.path=/tmp/x.bin", "data.source=memmap"],
    ):
        assert get_config("tiny", order).data.source == "memmap"
    with pytest.raises(ValueError, match="requires data.path"):
        get_config("tiny", ["data.source=memmap"])

    with pytest.raises(ValueError, match="learning_rate"):
        OptimizerConfig(learning_rate=0.0)
    with pytest.raises(ValueError, match="schedule"):
        OptimizerConfig(schedule="sawtooth")
    with pytest.raises(ValueError, match="b2"):
        OptimizerConfig(b2=1.0)
    with pytest.raises(ValueError, match="batch_size"):
        DataConfig(batch_size=0)
    with pytest.raises(ValueError, match="coordinator_address"):
        RuntimeConfig(num_processes=2)
    with pytest.raises(ValueError, match="process_id"):
        RuntimeConfig(num_processes=2, process_id=5,
                      coordinator_address="h:1234")
    with pytest.raises(ValueError, match="platform"):
        RuntimeConfig(platform="abacus")
    with pytest.raises(ValueError, match="moment_dtype"):
        OptimizerConfig(moment_dtype="flaot32")
