#!/usr/bin/env python3
"""Drive the Heroes engine's main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the 4-device cohort mesh vs one device

With no option the script runs, in one process:

1. transformer: Heroes on the registered ``"transformer"`` model at full
   composed width (``max_width=3``, ``d_base=128`` so a composed block is
   one 128-lane tile), cohort trainer, collective merge,
   ``forward_impl="auto"``, 3 rounds and an eval;
2. serving: the width-3 model composed once, greedy decode through the
   compiled Pallas decode kernel against the inline XLA reference;
3. image: Heroes on the default image model ``cnn`` at width 3, 2 rounds;
   its conv layers run the fused conv rank kernel (grow_out stem,
   square mode, stride 2);
4. kernel parity: each main-path kernel at the shapes the phases above
   produce, against its oracle in ``repro.kernels.ref``.

``--chips 4`` runs only the transformer engine twice in one process: on
the default cohort mesh over four devices (sharded cohort trainer plus the
``shard_map``/``psum`` merge), then pinned to one device, for one round
and an eval each, and compares them.

It refuses a host without a TPU.  Every check raises on failure; only when
all pass does the last line of standard output read
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
``--cpu-rehearsal`` runs the same phases at tiny sizes on the CPU with
interpret-mode kernels (the test suite's rehearsal); it never prints
``"ok"``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

# Tolerances, each with its reason.
#
# KERNEL_TOL: kernel vs oracle, as max|got - want| / max|want|.  The
# oracle runs under default_matmul_precision("highest"); the kernels
# multiply in the MXU, which may round float32 operands to bfloat16
# (8 mantissa bits, relative 2^-9 per operand).  The rank-path kernels
# chain two such contractions, so a few 1e-3 of the output scale is the
# expected error; a wrong index or layout is O(1).
KERNEL_TOL = 1e-2
# DECODE_LOGIT_TOL: Pallas decode vs the inline XLA decode, as
# max|dlogits| / max|logits|.  Both share every projection; they differ
# in the attention contraction, where XLA's default TPU precision rounds
# q, k, p and v to bfloat16 and the kernel's dots round differently.  Two
# layers of that, fed back through 8 decode steps, stay near 1e-2 of the
# logit scale.
DECODE_LOGIT_TOL = 2e-2
# MESH_PARAM_TOL: merged params on the 4-device mesh vs one device, per
# leaf as max|diff| / max|one-device leaf|.  Each device compiles its own
# cohort step for 2 clients instead of 8, and XLA tiles those default-
# precision float32 dots differently: round 1's loss before any training
# already differs in the 6th digit.  So the two runs differ at bfloat16
# rounding (the scale KERNEL_TOL admits), carried through the rounds'
# SGD, not at float32 re-association.  The log also gives the
# difference over the size of the rounds' update.
MESH_PARAM_TOL = 1e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    model_kw: dict  # make_transformer overrides
    clients: int
    per_round: int
    text_rounds: int
    image_rounds: int
    mesh_rounds: int
    tau_fixed: int  # round-0 local steps (Heroes Alg. 1)
    tau_max: int  # cap on Heroes' adaptive tau
    prompt: tuple  # (batch, prompt length) for serving
    decode_steps: int


# tau_max bounds the unrolled cohort step (tau is padded to a power of
# two and the scan is unrolled): with tau in {1, 2} the rounds reuse a
# few compiled steps instead of compiling one per new tau bucket.
# mesh_rounds: each run of the four-chip comparison compiles its own
# programs, about 100 s of round 1 on a v5e, and four chips are charged
# four times; round 1 already runs the sharded step, the psum merge and
# an eval over the mesh.
CHIP = Sizes(model_kw={"d_base": 128}, clients=32, per_round=8,
             text_rounds=3, image_rounds=2, mesh_rounds=1, tau_fixed=2,
             tau_max=2, prompt=(2, 8), decode_steps=8)
REHEARSAL = Sizes(model_kw={"d_base": 16, "n_layers": 1}, clients=8,
                  per_round=4, text_rounds=2, image_rounds=1, mesh_rounds=2,
                  tau_fixed=1, tau_max=2, prompt=(2, 4), decode_steps=3)
MAX_WIDTH = 3


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite values")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ---------------------------------------------------------------------------
# engine phases
# ---------------------------------------------------------------------------


def build_engine(kind: str, sz: Sizes, seed: int, rounds: int = 0,
                 **cfg_kw):
    """A Heroes runner on ``kind`` ("text": transformer, "image": cnn)
    through the public setup entry points, recording every round's
    assignments and client results.  ``rounds`` (default: the kind's
    round count in ``sz``) sets when the eval runs."""
    from repro.fl import (FLConfig, build_image_setup, build_runner,
                          build_text_setup)

    if kind == "text":
        model, px, py, test = build_text_setup(
            num_clients=sz.clients, max_width=MAX_WIDTH, seed=seed,
            model_name="transformer", model_kw=sz.model_kw)
        rounds = rounds or sz.text_rounds
    else:
        model, px, py, test = build_image_setup(
            model_name="cnn", num_clients=sz.clients, max_width=MAX_WIDTH,
            seed=seed)
        rounds = rounds or sz.image_rounds
    cfg = FLConfig(num_clients=sz.clients, clients_per_round=sz.per_round,
                   trainer="cohort", agg_backend="collective",
                   forward_impl="auto", tau_fixed=sz.tau_fixed,
                   tau_max=sz.tau_max, eval_every=rounds, seed=seed,
                   **cfg_kw)
    eng = build_runner("heroes", model, px, py, test, cfg=cfg, seed=seed)
    eng.recorded = []
    train_all = eng.trainer.train_all

    def recording(state, assigns):
        results = train_all(state, assigns)
        eng.recorded.append((assigns, results))
        return results

    eng.trainer.train_all = recording
    return eng, rounds


def run_rounds(eng, rounds: int, label: str):
    """Run ``rounds`` Heroes rounds; check every client loss is finite and
    that local training lowered the mean first-batch loss each round."""
    import numpy as np

    logs = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        rl = eng.run_round()
        dt = time.perf_counter() - t0
        assigns, results = eng.recorded[-1]
        before = np.array([r.loss_before for r in results.values()])
        after = np.array([r.loss_after for r in results.values()])
        check(bool(np.isfinite(before).all() and np.isfinite(after).all()),
              f"{label} round {rl.round}: non-finite client loss")
        check(after.mean() < before.mean(),
              f"{label} round {rl.round}: mean loss_after {after.mean()} "
              f"not below loss_before {before.mean()}")
        widths = sorted(a["width"] for a in assigns.values())
        taus = sorted(a["tau"] for a in assigns.values())
        log(f"  {label} round {rl.round}: loss {before.mean():.6f} -> "
            f"{after.mean():.6f}  widths {widths}  taus {taus}  "
            f"virtual clock {rl.wall_time!r}  acc {rl.accuracy}  "
            f"host wall {dt:.3f}s (compile included)")
        logs.append(rl)
    acc = logs[-1].accuracy
    check(acc is not None and 0.0 <= acc <= 1.0,
          f"{label}: eval accuracy {acc!r} after round {logs[-1].round}")
    return logs


def report_dispatch(eng, data_shape) -> dict:
    """Print the calibration ``auto`` dispatch used and its per-layer
    choice at every width."""
    from repro.core.calibration import for_dispatch, get_calibration

    log(f"  measured calibration: {get_calibration()}")
    cal = for_dispatch(eng.cfg)
    impls = {}
    for w in range(1, MAX_WIDTH + 1):
        impls[w] = eng.model.layer_impls(w, data_shape[0], "auto",
                                         tuple(data_shape), cal)
        log(f"  auto impls at width {w}: {impls[w]}")
    return impls


def cohort_step_text(eng, width: int) -> str:
    """Compiled text of the single-device cohort train step as the last
    round ran it at ``width``, lowered again from that group's shapes."""
    import jax
    import jax.numpy as jnp

    from repro.core.calibration import for_dispatch
    from repro.fl.engine.trainers import _cohort_fns, _next_pow2

    cfg, model = eng.cfg, eng.model
    assigns = next(a for a, _ in reversed(eng.recorded)
                   if any(x["width"] == width for x in a.values()))
    group = [(n, a) for n, a in assigns.items() if a["width"] == width]
    b_eff = min(cfg.batch_size, eng.data.num_samples(group[0][0]))
    group = [(n, a) for n, a in group
             if min(cfg.batch_size, eng.data.num_samples(n)) == b_eff]
    # the trainer's bucketing (CohortTrainer._prepare_group_inner)
    taus = [max(a["tau"], 1) for _, a in group]
    tau_pad = taus[0] if len(set(taus)) == 1 else _next_pow2(max(taus))
    c = len(group) if len(group) == cfg.clients_per_round \
        else _next_pow2(len(group))
    params = eng.aggregator.client_params(eng.state, *group[0])
    stacked = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct((c,) + v.shape, v.dtype), params)
    lead = (tau_pad, c, b_eff)
    x0 = eng.test_batch[model.input_key]
    y0 = eng.test_batch["labels"]
    batches = {model.input_key: jax.ShapeDtypeStruct(lead + x0.shape[1:],
                                                     x0.dtype),
               "labels": jax.ShapeDtypeStruct(lead + y0.shape[1:],
                                              jnp.int32)}
    taus = jax.ShapeDtypeStruct((c,), jnp.int32)
    train_fn, _ = _cohort_fns(model, width, True, None, cfg.forward_impl,
                              for_dispatch(cfg))
    return train_fn.lower(stacked, batches, taus,
                          cfg.lr).compile().as_text()


def transformer_phase(sz: Sizes, seed: int, on_chip: bool):
    import numpy as np

    log("== transformer phase")
    eng, rounds = build_engine("text", sz, seed)
    with eng:
        seq = eng.test_batch["tokens"].shape[1]
        report_dispatch(eng, (eng.cfg.batch_size, seq))
        run_rounds(eng, rounds, "transformer")
        w = max(a["width"] for asg, _ in eng.recorded for a in asg.values())
        n = cohort_step_text(eng, w).count("tpu_custom_call")
        log(f"  cohort train step width {w}: {n} tpu_custom_call")
        check(n > 0 or not on_chip,
              f"cohort step at width {w} runs no Pallas kernel")
        test_tokens = np.asarray(eng.test_batch["tokens"])
        return eng.model, eng.params, test_tokens


def serving_phase(model, params, test_tokens, sz: Sizes, on_chip: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fl.transformer import (_decode_step, arch_of, greedy_decode,
                                      serving_weights)

    log("== serving phase")
    weights = serving_weights(model, params, MAX_WIDTH)
    B, t0 = sz.prompt
    prompt = test_tokens[:B, :t0]
    toks_p, logits_p = greedy_decode(model, weights, MAX_WIDTH, prompt,
                                     sz.decode_steps, backend="pallas")
    toks_x, logits_x = greedy_decode(model, weights, MAX_WIDTH, prompt,
                                     sz.decode_steps, backend="xla")
    log(f"  pallas tokens {toks_p.tolist()}")
    log(f"  xla    tokens {toks_x.tolist()}")
    check(np.array_equal(toks_p, toks_x), "decode tokens differ")
    err = rel_err(logits_p, logits_x)
    log(f"  logits rel err {err!r} (tol {DECODE_LOGIT_TOL})")
    check(err <= DECODE_LOGIT_TOL, f"decode logits rel err {err}")
    arch = arch_of(model)
    heads = MAX_WIDTH * arch.heads_base
    smax = t0 + sz.decode_steps
    cache = [jax.ShapeDtypeStruct((B * heads, smax, arch.head_dim),
                                  jnp.float32)] * arch.n_layers
    txt = _decode_step.lower(
        weights, cache, cache, jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32), model=model, width=MAX_WIDTH,
        backend="pallas", interpret=not on_chip).compile().as_text()
    n = txt.count("tpu_custom_call")
    log(f"  decode step: {n} tpu_custom_call")
    check(n > 0 or not on_chip, "decode step runs no Pallas kernel")


def image_phase(sz: Sizes, seed: int, on_chip: bool):
    log("== image phase")
    # conv_rank_overhead pinned to the plain FLOPs model (1.0): auto then
    # takes the fused conv rank kernel wherever FLOPs favour it (every
    # conv from width 2 up), whatever the host's calibration measures.
    # This phase exists to run that kernel.
    eng, rounds = build_engine("image", sz, seed, conv_rank_overhead=1.0)
    with eng:
        x = eng.test_batch["x"]
        impls = report_dispatch(eng, (eng.cfg.batch_size,) + x.shape[1:])
        run_rounds(eng, rounds, "cnn")
        trained = {a["width"] for asg, _ in eng.recorded for a in asg.values()}
        # by FLOPs the rank path wins from width 2 up; each conv layer must
        # have run it at some width the rounds trained
        for name in ("conv1", "conv2", "conv3"):
            check(any(impls[w][name] == "rank_space" for w in trained),
                  f"cnn {name} ran no conv rank kernel at widths {trained}")
        n = cohort_step_text(eng, max(trained)).count("tpu_custom_call")
        log(f"  cnn cohort train step width {max(trained)}: {n} "
            "tpu_custom_call")
        check(n > 0 or not on_chip, "cnn cohort step runs no Pallas kernel")
        return eng.model, x.shape[1]


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------


def kernel_phase(text_model, seq: int, image_model, hw: int, sz: Sizes,
                 seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.composition import gather_blocks, init_factors
    from repro.kernels import ref
    from repro.kernels.compose import (_u2_layout, compose_apply_pallas,
                                       compose_pallas, rank_apply_pallas)
    from repro.kernels.conv_rank import _u2_conv_layout, conv_rank_pallas
    from repro.kernels.decode_attention import decode_attention_pallas
    from repro.fl.transformer import arch_of

    log("== kernel parity")
    key = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    p = MAX_WIDTH
    C = sz.per_round
    B = 16  # FLConfig.batch_size
    T = seq

    def factors(spec):
        v, u = init_factors(next(key), spec)
        return v, gather_blocks(u, np.arange(spec.blocks_for_width(p)))

    def oracle(fn, *a):
        with jax.default_matmul_precision("highest"):
            return fn(*a)

    def compare(name, got, want):
        err = rel_err(got, want)
        log(f"  {name}: rel err {err!r} (tol {KERNEL_TOL})")
        check(err <= KERNEL_TOL, f"{name}: rel err {err}")

    # compose: the square hidden projection and the grow_in head
    for lname in ("l0.wq", "l0.up", "head"):
        v, u = factors(text_model.specs[lname])
        compare(f"compose_pallas {lname}", compose_pallas(v, u),
                oracle(ref.compose_ref, v, u))

    # dense rank path and fused compose+apply at the transformer layers
    # (M = batch x sequence rows) and the cnn head (M = batch rows)
    dense = [("l0.wq", text_model, B * T), ("l0.down", text_model, B * T),
             ("head", text_model, B * T), ("fc", image_model, B)]
    for lname, model, M in dense:
        spec = model.specs[lname]
        v, u = factors(spec)
        g = 1 if spec.mode == "grow_out" else p
        x = jax.random.normal(next(key), (M, spec.weight_shape(p)[1]))
        want = oracle(ref.compose_apply_ref, x, v, u, p, spec.mode)
        xg = x.reshape(M, g, -1)
        u2 = _u2_layout(u, p, spec.mode)
        compare(f"rank_apply_pallas {lname}",
                rank_apply_pallas(xg, v[0], u2), want)
        compare(f"compose_apply_pallas {lname}",
                compose_apply_pallas(xg, v[0], u2.reshape(g, spec.rank, -1)),
                want)
        if lname == "l0.wq":  # the cohort trainer's vmapped form
            vs, us, xs = [], [], []
            for _ in range(C):
                vc, uc = factors(spec)
                vs.append(vc), us.append(uc)
                xs.append(jax.random.normal(next(key), x.shape))
            vs, us, xs = jnp.stack(vs), jnp.stack(us), jnp.stack(xs)
            got = jax.vmap(lambda x_, v_, u_: rank_apply_pallas(
                x_.reshape(M, g, -1), v_[0], _u2_layout(u_, p, spec.mode)))(
                    xs, vs, us)
            want = oracle(jax.vmap(lambda x_, v_, u_: ref.compose_apply_ref(
                x_, v_, u_, p, spec.mode)), xs, vs, us)
            compare(f"rank_apply_pallas {lname} vmap C={C}", got, want)

    # conv rank path at the cnn's three convs (stem, stride-2 square x2)
    in_hw = {"conv1": hw, "conv2": hw, "conv3": hw // 2}
    for lname, stride in (("conv1", 1), ("conv2", 2), ("conv3", 2)):
        spec = image_model.specs[lname]
        v, u = factors(spec)
        h = in_hw[lname]
        x = jax.random.normal(next(key), (B, h, h, spec.weight_shape(p)[1]))
        u2 = _u2_conv_layout(u, p, spec.mode)
        compare(f"conv_rank_pallas {lname} ({spec.mode}, stride {stride})",
                conv_rank_pallas(x, v, u2, p=p, mode=spec.mode,
                                 stride=stride),
                oracle(ref.conv_rank_ref, x, v, u, p, spec.mode, stride))
        if lname == "conv2":
            xs = jax.random.normal(next(key), (C,) + x.shape)
            got = jax.vmap(lambda x_: conv_rank_pallas(
                x_, v, u2, p=p, mode=spec.mode, stride=stride))(xs)
            want = oracle(jax.vmap(lambda x_: ref.conv_rank_ref(
                x_, v, u, p, spec.mode, stride)), xs)
            compare(f"conv_rank_pallas {lname} vmap C={C}", got, want)

    # decode attention at the serving phase's cache
    arch = arch_of(text_model)
    bh = sz.prompt[0] * p * arch.heads_base
    smax = sz.prompt[1] + sz.decode_steps
    q = jax.random.normal(next(key), (bh, arch.head_dim))
    k = jax.random.normal(next(key), (bh, smax, arch.head_dim))
    v = jax.random.normal(next(key), (bh, smax, arch.head_dim))
    lengths = jnp.asarray(np.arange(bh) % smax + 1, jnp.int32)
    compare("decode_attention_pallas",
            decode_attention_pallas(q, k, v, lengths),
            oracle(ref.decode_attention_ref, q, k, v, lengths))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def mesh_phase(sz: Sizes, seed: int, chips: int):
    import jax
    import numpy as np

    log(f"== {chips}-device cohort mesh vs one device")
    runs = {}
    for devices in (0, 1):
        eng, rounds = build_engine("text", sz, seed, sz.mesh_rounds,
                                   trainer_mesh_devices=devices,
                                   agg_devices=devices)
        with eng:
            mesh = eng.trainer.mesh
            size = 1 if mesh is None else mesh.devices.size
            merge = eng.merger.mesh
            msize = 1 if merge is None else merge.devices.size
            log(f"  run trainer_mesh_devices={devices}: trainer mesh "
                f"{size} devices, merge mesh {msize} devices")
            check(size == msize == (chips if devices == 0 else 1),
                  f"expected {chips if devices == 0 else 1} devices, got "
                  f"trainer {size} merge {msize}")
            init = jax.tree_util.tree_map(np.asarray, eng.params)
            logs = run_rounds(eng, rounds, f"mesh{size}")
            taus = [{int(n): (a["width"], a["tau"]) for n, a in asg.items()}
                    for asg, _ in eng.recorded]
            params = jax.tree_util.tree_map(np.asarray, eng.params)
            runs[size] = (logs, taus, params)
    (logs4, taus4, p4), (logs1, taus1, p1) = runs[chips], runs[1]
    fields = ("round", "wall_time", "traffic_bytes", "makespan", "avg_wait",
              "mean_tau", "up_bytes", "down_bytes")
    for a, b in zip(logs4, logs1):
        for f in fields:
            check(getattr(a, f) == getattr(b, f),
                  f"round {a.round} {f}: {getattr(a, f)!r} on {chips} "
                  f"devices vs {getattr(b, f)!r} on one")
        log(f"  round {a.round}: " + ", ".join(
            f"{f}={getattr(a, f)!r}" for f in fields[1:]) + " (equal)")
    check(taus4 == taus1, f"(width, tau) assignments differ: {taus4} vs "
          f"{taus1}")
    log(f"  (width, tau) per client equal in all {len(taus1)} rounds")
    errs = {jax.tree_util.keystr(path): rel_err(a, b) for (path, a), b in
            zip(jax.tree_util.tree_leaves_with_path(p4),
                jax.tree_util.tree_leaves(p1))}
    for name, err in errs.items():
        log(f"  merged {name}: rel err {err!r}")
    f4, f1, f0 = (np.concatenate([x.ravel().astype(np.float64)
                                  for x in jax.tree_util.tree_leaves(t)])
                  for t in (p4, p1, init))
    ratio = float(np.linalg.norm(f4 - f1) / np.linalg.norm(f1 - f0))
    log(f"  |params({chips}) - params(1)| / |params(1) - init|: {ratio!r}")
    worst = max(errs, key=errs.get)
    check(errs[worst] <= MESH_PARAM_TOL,
          f"merged params {worst}: rel err {errs[worst]} > {MESH_PARAM_TOL}")
    log(f"  merged params: worst leaf {worst} rel err {errs[worst]!r} "
        f"(tol {MESH_PARAM_TOL})")


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cohort-mesh comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU with interpret kernels "
                         "(for the test suite); never reports ok")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"  # never take a chip
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.kernels.compose import default_interpret

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"jax {jax.__version__}  device_kind {dev['kind']!r}  "
        f"count {dev['count']}  platform {dev['platform']}")
    on_chip = not args.cpu_rehearsal
    if on_chip:
        check(dev["platform"] == "tpu",
              f"no TPU: jax.devices()[0].platform is {dev['platform']!r}")
        check(not default_interpret(), "kernels would run in interpret mode")
        log(f"compile cache: {enable_compile_cache()}")
    sz = REHEARSAL if args.cpu_rehearsal else CHIP

    t0 = time.perf_counter()
    if args.chips > 1:
        check(dev["count"] == args.chips,
              f"--chips {args.chips} but {dev['count']} devices")
        mesh_phase(sz, args.seed, args.chips)
    else:
        model, params, test_tokens = transformer_phase(sz, args.seed, on_chip)
        serving_phase(model, params, test_tokens, sz, on_chip)
        image_model, hw = image_phase(sz, args.seed, on_chip)
        kernel_phase(model, test_tokens.shape[1], image_model, hw, sz,
                     args.seed)
    log(f"all checks passed in {time.perf_counter() - t0:.1f}s host wall")
    if on_chip:
        print(json.dumps({"ok": True, "device": dev}), flush=True)
    else:
        print(json.dumps({"rehearsal": "passed", "device": dev}), flush=True)


if __name__ == "__main__":
    main()
