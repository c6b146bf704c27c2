"""Bring-up guards (ISSUE 21): a run that did not reach the chip, did not
compile its kernels, or did not complete its requests must not look green.

- ``chip_smoke.py`` has no CPU mode: held to the CPU it exits non-zero
  within seconds and names the platform it found.
- ``kernels="pallas"`` means Mosaic-compiled, always: on the CPU backend it
  raises; only ``pallas_interpret`` reaches the interpreter.
- ``generate.py`` exits non-zero when a request ends in a typed error.
- ``runtime.platform`` is a requirement; the peaks table never invents a
  number; the compile cache can be placed from outside.
"""

import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_chip_smoke_fails_fast_without_a_chip():
    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "'cpu'" in r.stderr, r.stderr           # the platform it found
    assert '"ok"' not in r.stdout, r.stdout        # and no result line


def test_pallas_on_cpu_raises_and_only_interpret_interprets():
    from orion_tpu import ops
    from orion_tpu.config import get_config
    from orion_tpu.models import forward, init_params

    q = jnp.ones((1, 16, 2, 8), jnp.float32)
    with pytest.raises(RuntimeError, match="backend is 'cpu'"):
        ops.attention(q, q, q, impl="pallas")
    with pytest.raises(RuntimeError, match="backend is 'cpu'"):
        ops.rmsnorm(jnp.ones((4, 8)), jnp.ones((8,)), impl="pallas")
    S = ops.rope.KERNEL_MIN_SEQ      # from here up the kernel rotates
    with pytest.raises(RuntimeError, match="backend is 'cpu'"):
        ops.apply_rope(
            jnp.ones((1, S, 2, 8), jnp.float32), jnp.arange(S), impl="pallas")
    out = ops.attention(q, q, q, impl="pallas_interpret")
    assert out.shape == q.shape

    # The model path: a `pallas` preset lowered on the CPU raises too.
    cfg = get_config("tiny-llama", ["model.kernels=pallas"]).model
    params = init_params(cfg, jax.random.key(0))
    with pytest.raises(RuntimeError, match="backend is 'cpu'"):
        forward(params, jnp.zeros((1, 16), jnp.int32), cfg)


def test_generate_exit_code_follows_request_outcomes(monkeypatch, capsys):
    """An injected NaN quarantines one request ("error:nan"): the CLI
    prints the tag AND returns non-zero; the fault-free run returns 0."""
    import generate
    import orion_tpu.infer as infer
    from orion_tpu.runtime.fault import FaultInjector, FaultSpec

    argv = ["--preset", "tiny-llama", "--tokens", "5,3,9", "--tokens",
            "1,2", "--max-new-tokens", "24", "runtime.platform=cpu",
            "inference.nan_guard=true"]
    assert generate.main(argv) == 0
    capsys.readouterr()

    real = infer.InferenceEngine

    def faulty(cfg, params, **kw):
        return real(cfg, params, fault_injector=FaultInjector(
            [FaultSpec("nan", step=1)]), **kw)

    monkeypatch.setattr(infer, "InferenceEngine", faulty)
    assert generate.main(argv) == 1
    io = capsys.readouterr()
    assert "[error:nan]" in io.out
    assert "did not complete" in io.err


def test_runtime_platform_is_a_requirement():
    from orion_tpu.config import RuntimeConfig
    from orion_tpu.runtime import initialize

    info = initialize(RuntimeConfig(platform="cpu"))
    assert info.platform == "cpu"
    with pytest.raises(RuntimeError, match="held to platform.*'cpu'"):
        initialize(RuntimeConfig(platform="tpu"))


def test_peaks_table_is_exact_and_never_invents_a_number():
    from orion_tpu.metrics import DEVICE_PEAKS, MetricsLogger, device_peaks

    class Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    assert device_peaks(jax.devices("cpu")[0]) is None
    assert device_peaks(Dev("tpu", "TPU v5 lite")).bf16_flops == 197e12
    assert device_peaks(Dev("tpu", "TPU v5 lite")).hbm_bytes_per_s == 819e9
    # Exact keys: "TPU v5" must not match "TPU v5 lite" or "TPU v5p".
    with pytest.raises(KeyError, match="TPU v5'"):
        device_peaks(Dev("tpu", "TPU v5"))
    assert all(p.source for p in DEVICE_PEAKS.values())

    # CPU: throughput is still reported, MFU is "not measured" (None).
    log = MetricsLogger(1e9, 1, jax.devices("cpu")[0], log_interval=10**9)
    m = log.record(step=1, loss=1.0, tokens=100, step_time_s=0.5)
    assert m.mfu is None and m.tokens_per_sec == 200.0
    assert m.to_dict()["mfu"] is None


_CACHE_PROBE = (
    "import jax; from orion_tpu.runtime import enable_compile_cache; "
    "print(enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)"
)


def _cache_dirs(cwd, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT)
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120, check=True,
    )
    return r.stdout.split()


def test_compile_cache_placement(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins where set; unset, the cache is one
    fixed in-checkout path whatever the working directory."""
    outside = str(tmp_path / "cache")
    assert _cache_dirs(tmp_path, outside) == [outside, outside]
    (tmp_path / "elsewhere").mkdir()
    a = _cache_dirs(tmp_path, None)
    b = _cache_dirs(tmp_path / "elsewhere", None)
    assert a == b == [str(ROOT / ".jax_compile_cache")] * 2
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_compile_cache/" in ignored
