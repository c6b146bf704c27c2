"""Traffic kind ``train``: the trainer's own jitted, donated step
(``orion_tpu.train.Trainer.train_step``) on synthetic token batches from the
seed. The weights, the batches, the clock and the output check are the
benchmark's; the step program, its mesh and its shardings are the program's."""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.harness.cell import Outcome, Phases, key_of

clock = time.monotonic


def _batches(trainer, cfg, mix: dict, seed: int) -> list:
    """``distinct_batches`` token batches from the seed, on the device in the
    step's own batch sharding."""
    import jax

    rng = np.random.default_rng(seed)
    B, S = cfg.data.batch_size, mix["seq_len"]
    out = []
    for _ in range(mix["distinct_batches"]):
        seq = rng.integers(1, cfg.model.vocab_size, size=(B, S + 1),
                           dtype=np.int32)
        host = {"inputs": seq[:, :-1], "targets": seq[:, 1:]}
        out.append(jax.tree.map(
            lambda v: jax.make_array_from_process_local_data(
                trainer.batch_shard, np.ascontiguousarray(v)), host))
    return out


def _sample_paths(params, names: list) -> list:
    """The tree paths of the gradient leaves the configuration names
    (``blocks.attn.wk`` ...). The sample is the configuration's, not the
    run's seed's: one program for every run, so the compile cache holds it."""
    import jax

    by_name = {
        ".".join(str(k.key) for k in p): p
        for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    missing = [n for n in names if n not in by_name]
    if missing:
        raise SystemExit(f"no parameter leaves {missing}; have {sorted(by_name)}")
    return [by_name[n] for n in names]


def _split(params, chosen):
    """(sampled leaves, the other leaves, function that rebuilds the tree
    from both). The other leaves travel as an ARGUMENT: closed over, they
    would be baked into the program as gigabytes of constants."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    idx = [i for i, (p, _) in enumerate(flat) if p in chosen]
    rest = [None if i in idx else v for i, (_, v) in enumerate(flat)]

    def merge(sub, rest_):
        full = list(rest_)
        for i, v in zip(idx, sub):
            full[i] = v
        return jax.tree_util.tree_unflatten(treedef, full)

    return [flat[i][1] for i in idx], rest, merge


def check_numbers(trainer, cfg, ref, hf, state, batch, control=None,
                  phases=None) -> dict:
    """Loss and a sample of gradient leaves of the FIRST step: the
    program's own loss function (its kernels, scan, remat and mesh) against
    the float32 reference ``ref`` (the cell's ``reference()``) on the same
    weights and batch."""
    import jax

    from orion_tpu.models.transformer import loss_fn

    import dataclasses

    params = state["params"]
    mcfg, mesh = cfg.model, trainer.mesh
    depth = hf["correct"].get("check_layers", mcfg.n_layers)
    if depth < mcfg.n_layers:
        # The first ``check_layers`` layers of the same weights, on the same
        # mesh in the same shardings: the float32 reference of the whole
        # depth does not fit beside the train state (PERF.md).
        cut = jax.jit(lambda b: jax.tree.map(lambda a: a[:depth], b),
                      out_shardings=trainer.shardings["params"]["blocks"])
        params = dict(params, blocks=cut(params["blocks"]))
        mcfg = dataclasses.replace(mcfg, n_layers=depth)
        hf = {**hf, key_of(hf, "n_layers"): depth}
    chosen = _sample_paths(params, hf["correct"]["grad_leaves"])
    sub, rest, merge = _split(params, chosen)

    def program(sub_, rest_, b):
        return loss_fn(merge(sub_, rest_), b, mcfg, mesh)[0]

    sharded = {}
    if mesh.size > 1:      # every chip its own sequences (see ref.loss)
        sharded = dict(
            mesh=mesh, batch_spec=trainer.batch_shard.spec,
            param_specs=jax.tree.map(lambda s: s.spec,
                                     trainer.shardings["params"]))

    def reference(sub_, rest_, b, quant=None):
        return ref.loss(merge(sub_, rest_), b["inputs"], b["targets"], hf,
                        quant, **sharded)

    p_loss, p_grads = jax.block_until_ready(
        jax.jit(jax.value_and_grad(program))(sub, rest, batch))
    if phases is not None:
        phases.mark("check: program")
    r_loss, r_grads = jax.block_until_ready(
        jax.jit(jax.value_and_grad(reference))(sub, rest, batch))
    if phases is not None:
        phases.mark("check: reference")

    @jax.jit
    def worst(grads, ref_grads):       # on the device: the leaves are large
        import jax.numpy as jnp

        f32 = jnp.float32
        return jnp.max(jnp.stack([
            jnp.linalg.norm((g.astype(f32) - r.astype(f32)).ravel())
            / jnp.linalg.norm(r.astype(f32).ravel())
            for g, r in zip(grads, ref_grads)]))

    def numbers(loss, grads):
        return {
            "loss_rel_err": abs(float(loss) - float(r_loss)) / abs(float(r_loss)),
            "grad_rel_err_max": float(worst(grads, r_grads)),
        }

    out = {"sound": numbers(p_loss, p_grads),
           "leaves": [jax.tree_util.keystr(p) for p in chosen]}
    if control is not None:
        c_loss, c_grads = jax.jit(
            jax.value_and_grad(lambda s, r, b: reference(s, r, b, control))
        )(sub, rest, batch)
        out["control"] = numbers(c_loss, c_grads)
    return out


def build(cell, dev, seed: int):
    import jax

    from benchmarks.reference import weights
    from orion_tpu.train import Trainer
    from orion_tpu.train.optimizer import init_opt_state

    cfg = cell.program_config()
    trainer = Trainer(cfg)
    if trainer.mesh.size != cell.chips:
        raise SystemExit(f"the mesh has {trainer.mesh.size} devices, the "
                         f"cell {cell.chips}")
    sh = trainer.shardings
    params = weights.for_cell(cell, cfg, seed, out_shardings=sh["params"])

    def rest(p):
        import jax.numpy as jnp

        return {"params": p, "opt": init_opt_state(p, cfg.optimizer),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.jit(rest, out_shardings=sh, donate_argnums=(0,))(params)
    return cfg, trainer, state


def decide(numbers: dict, limits: dict) -> tuple[bool, list]:
    checks = [(k, numbers[k], limits[k]) for k in sorted(limits)]
    ok = all(lim is not None and math.isfinite(v) and v <= lim
             for _, v, lim in checks)
    return ok, checks


def run(cell, dev, *, seed: int, seconds: float, trace: bool,
        t_process: float, compiles) -> Outcome:
    import jax

    mix, hf = cell.mix, cell.config
    phases = Phases(t_process)
    phases.mark("imports")
    cfg, trainer, state = build(cell, dev, seed)
    jax.block_until_ready(state)
    phases.mark("trainer+weights")
    batches = _batches(trainer, cfg, mix, seed)
    numbers = check_numbers(trainer, cfg, cell.reference(), hf, state,
                            batches[0], phases=phases)
    phases.mark("check: compared")
    correct, checks = decide(numbers["sound"], hf["correct"]["limits"])
    print(f"gradient leaves compared: {numbers['leaves']}; seen and not "
          f"judged: loss_rel_err = {numbers['sound']['loss_rel_err']!r}",
          flush=True)

    step = trainer.train_step
    for i in range(mix["warm_steps"]):
        state, m = step(state, batches[i % len(batches)])
    jax.block_until_ready(m)
    phases.mark("warm steps")
    phases.say()
    n_setup, compile_s = compiles.take()
    tokens_per_step = cfg.data.batch_size * mix["seq_len"]

    def steps_until(stop, annotate=False):
        """Dispatch steps until ``stop()``; at most ``max_in_flight`` run
        ahead of the host. The last step is synchronised before return."""
        nonlocal state
        pending, losses, n = [], [], 0
        while not stop(n):
            if annotate:
                with jax.profiler.TraceAnnotation("bench.train_step"):
                    state, m = step(state, batches[n % len(batches)])
            else:
                state, m = step(state, batches[n % len(batches)])
            pending.append(m)
            losses.append(m["loss"])
            n += 1
            if len(pending) >= mix["max_in_flight"]:
                jax.block_until_ready(pending.pop(0))
        jax.block_until_ready(pending)
        return n, losses

    t_open = clock()
    setup_s = t_open - t_process
    n_steps, losses = steps_until(lambda n: clock() - t_open >= seconds)
    window_s = clock() - t_open
    n_window, _ = compiles.take()
    losses = [float(x) for x in jax.device_get(losses)]
    finite = all(math.isfinite(x) for x in losses)
    checks.append(("loss_finite_at_every_step", finite, True))

    device_extra, breakdown, trace_obs = {}, None, None
    if trace and dev.platform != "cpu":   # a CPU has no device trace
        from benchmarks.trace import capture

        with capture.Trace(cell) as tr:
            steps_until(lambda n: n >= mix["trace_steps"], annotate=True)
        trace_obs = tr.reduced(dev)
        trace_obs["steps"] = mix["trace_steps"]
        device_extra = {"busy_s": trace_obs["busy_s"],
                        "window_s": trace_obs["window_s"]}
        breakdown = trace_obs["breakdown"]

    e2e = {"setup_s": setup_s,
           "train_tokens_per_s_chip": n_steps * tokens_per_step / window_s
           / cell.chips}
    obs = {
        "window_s": window_s, "steps": n_steps, "chips": cell.chips,
        "tokens_per_step": tokens_per_step, "seq_len": mix["seq_len"],
        "compile_s": compile_s, "compiles_setup": n_setup,
        "compiles_in_window": n_window, "trace": trace_obs, "config": hf,
        "peaks": dev.peaks if dev.platform != "cpu" else None,
        "loss_first": losses[0] if losses else None,
        "loss_last": losses[-1] if losses else None,
    }
    print(f"window: {window_s:.3f}s {n_steps} steps, loss "
          f"{obs['loss_first']} -> {obs['loss_last']}", flush=True)
    return Outcome(correct and finite, checks, n_steps,
                   sum(not math.isfinite(x) for x in losses), e2e, obs,
                   device_extra, breakdown)
