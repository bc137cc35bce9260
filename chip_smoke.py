"""End-to-end check that the FlyMC sampler runs on a TPU chip.

    python chip_smoke.py [--seed 0]                  # one chip
    python chip_smoke.py --chips 4                   # the row- and
                                                     # chain-sharded paths

One process drives every phase (the chip belongs to one process) and
starts no other. With one chip, at the paper's three Table-1 shapes
(``benchmarks/table1.py``; rows made by ``repro.data`` from ``--seed``):

  parity   the z-candidate kernel against its jnp oracle, bitwise, and the
           bright-GLM kernel against its reference at the kernel tests'
           tolerances, at 1 and 8 chains;
  sampler  MAP-tuned FlyMC through ``api.sample`` with the fused engines
           (``backend="pallas"``, ``z_backend="fused"``) against
           ``api.regular_mcmc``: both kernels compiled into the chunk
           (``tpu_custom_call``), finite θ, 0 < n_bright < N, queries per
           iteration below N/4, and posterior means within
           ``Z_BOUND`` combined Monte Carlo standard errors in every
           coordinate the data determine (softmax on overlapping classes,
           see SEPARABLE; robust from an all-dark start, see DARK_START);
           one logistic run starts below its initial bright count,
           overflows, and must land bitwise on the ample run;
  serve    a ``repro.serve.Service`` drains four logistic jobs at the
           Table-1 shape with the fused engines, with no fault.

``--chips 4`` runs only the paths that exist across chips: the robust
problem with rows sharded four ways (``distributed.dist_algorithm``)
against the one-chip run, and an 8-chain ``chain_fleet`` against the same
chains batched on one chip by ``api.sample``.

Every phase prints what it measured. A failed check exits non-zero before
the last line, which is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Table-1 tolerances of tests/test_kernels.py::test_bright_glm.
RTOL, ATOL = 1e-4, 1e-5
# Posterior means of FlyMC and regular MCMC must agree within this many
# combined Monte Carlo standard errors, coordinate by coordinate.
Z_BOUND = 5.0
CHUNK = 200
# Adam steps for θ_MAP. Table 1's 400 leave the softmax problem 18 nats
# below its mode (log posterior −23.1 against −4.58 after 2,000 steps, on a
# CPU), so chains started there drift for thousands of iterations.
MAP_STEPS = 2_000
# Sampler runs per Table-1 problem: (chains, iterations, warmup), long
# enough that every coordinate's ESS supports its standard error. On a CPU
# (jnp engines, same chain law) these gave max |z| of 3.56 (logistic),
# 3.87 (softmax, see SEPARABLE) and 2.87 (robust, N = 200,000) over every
# coordinate; a few hundred
# iterations gave 5–13, because Geyer's ESS overstates a chain that has
# not yet mixed. RWMH in 51 dimensions and MALA in 768 mix slowly but cost
# little per iteration; robust slice sampling costs more and mixes faster.
RUNS = {
    "mnist-logistic-rwmh": (4, 10_000, 2_000),
    "cifar-softmax-mala": (4, 16_000, 4_000),
    "opv-robust-slice": (2, 4_000, 1_000),
}
# Problems whose Table-1 rows are separable, with the generator sharpness
# the sampler phase uses instead (parity keeps the Table-1 rows). At the
# default sharpness 3 the three classes' binary features never overlap:
# the likelihood is flat over a cone, the posterior is the prior's there,
# regular MALA accepts 99.96% of steps while still diffusing and FlyMC's
# spread is a fifth of regular's after 4,000 iterations (jnp engines on a
# CPU) — neither chain converges in any run this script could afford, so
# a posterior comparison there would test nothing. At sharpness 0.1 the
# classes overlap (log posterior −16,652 at the MAP, against −4.58).
SEPARABLE = {"cifar-softmax-mala": 0.1}
# Problems whose FlyMC chains start with every row dark (api.sample's
# init_state), with their (capacity, cand_capacity). The default start
# puts each row bright with probability 2·q_db: 36,000 rows at N = 1.8M,
# which the chain sheds only over hundreds of iterations while θ drifts
# off the MAP, and which holds the capacity the bright-GLM grid walks at
# 36,864 rows for the rest of the run. MAP-tuned chains keep a few dozen
# rows bright (jnp engines at N = 200,000 on a CPU).
DARK_START = {"opv-robust-slice": (4_096, 20_480)}


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise CheckFailed(what)


def say(phase: str, **fields):
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(jax.devices())}", file=sys.stderr)
        return 2

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.compile_cache import use_compile_cache

    say("setup", compile_cache=use_compile_cache(), device=dev.device_kind,
        chips=len(jax.devices()))
    try:
        if args.chips == 4:
            four_chips(args)
        else:
            parity(args)
            sampler(args)
            serve(args)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips,
    }}))
    return 0


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------


def _problems(seed):
    import jax

    from benchmarks.table1 import PROBLEMS

    return zip(PROBLEMS, jax.random.split(jax.random.key(seed), len(PROBLEMS)))


def _peak(device=None):
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _aot(fn, *args):
    """(outputs, compile seconds, run seconds) of ``jax.jit(fn)(*args)``."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    out, run_s = _timed(lambda: compiled(*args))
    return out, time.perf_counter() - t0 - run_s, run_s


def _posterior(theta, burn):
    """(means, mcse) per coordinate of a (chains, iters, ...) θ array.

    The MCSE is sd/√ESS, with the ESS of each coordinate summed over its
    chains (``core.diagnostics``' Geyer estimator per chain).
    """
    import numpy as np

    from repro.core import diagnostics

    s = np.asarray(theta, np.float64)[:, burn:]
    s = s.reshape(s.shape[0], s.shape[1], -1)
    flat = s.reshape(-1, s.shape[2])
    ess = np.array([
        sum(diagnostics.effective_sample_size(s[c, :, j])
            for c in range(s.shape[0]))
        for j in range(s.shape[2])
    ])
    return flat.mean(0), flat.std(0) / np.sqrt(ess)


def _identified(theta):
    """θ in the coordinates the data determine.

    Softmax θ (K, D) is identified only up to a common vector added to
    every class: the likelihood and the Böhning bound are invariant to it,
    so the class mean θ̄ has exactly its prior as posterior. It is compared
    as θ_k − θ̄ (every coordinate the data determine); θ̄ diffuses on the
    prior's scale, far slower than the data-determined directions mix.
    """
    import numpy as np

    theta = np.asarray(theta)
    if theta.ndim == 4:  # (chains, iters, K, D)
        return theta - theta.mean(axis=2, keepdims=True)
    return theta


def _agree(phase, name, a, b):
    """Means within Z_BOUND combined MCSEs in every coordinate."""
    import numpy as np

    (ma, ea), (mb, eb) = a, b
    z = np.abs(ma - mb) / np.sqrt(ea**2 + eb**2)
    say(phase, problem=name, coords=z.size, max_z=f"{z.max():.3f}",
        bound=Z_BOUND)
    check(bool(np.all(np.isfinite(z)) and z.max() <= Z_BOUND),
          f"{name}: posterior means differ by {z.max():.2f} MCSE "
          f"(> {Z_BOUND})")


# ---------------------------------------------------------------------------
# Phase 1: kernel parity on the chip
# ---------------------------------------------------------------------------


def parity(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.table1 import capacity_for
    from repro.core import bounds
    from repro.core.bounds import fused_family_of
    from repro.kernels.bright_glm.ops import bright_glm
    from repro.kernels.bright_glm.ref import bright_glm_ref
    from repro.kernels.z_update.ops import z_candidates
    from repro.kernels.z_update.ref import z_candidates_ref

    for prob, key in _problems(args.seed):
        model = prob.build(key, prob.n)
        data = bounds.with_gather_layout(model.data)
        fam = fused_family_of(model.bound)
        kw = model.bound.fused_kernel_kwargs()
        n, cap = prob.n, capacity_for(prob.n)
        for chains in (1, 8):
            ks = jax.random.split(jax.random.fold_in(key, chains), 4)
            # z-update: a random partition with 1% bright, at both Table-1
            # proposal rates (q = 0.1 overflows the buffer at N = 1.8M).
            arr = jax.vmap(lambda k: jax.random.permutation(k, n))(
                jax.random.split(ks[0], chains)).astype(jnp.int32)
            num = jnp.full((chains,), n // 100, jnp.int32)
            words = jax.random.randint(ks[1], (chains, 2), -2**31, 2**31 - 1,
                                       jnp.int32)
            for q in (prob.q_tuned, prob.q_untuned):
                fk = jax.vmap(lambda a, m, w: z_candidates(a, m, w, q, cap))
                fr = jax.vmap(
                    lambda a, m, w: z_candidates_ref(a, m, w, q, cap))
                (ck, nk), comp_s, run_s = _aot(fk, arr, num, words)
                cr, nr = jax.jit(fr)(arr, num, words)
                same = bool(np.array_equal(np.asarray(ck), np.asarray(cr))
                            and np.array_equal(np.asarray(nk), np.asarray(nr)))
                say("parity", kernel="z_candidates", problem=prob.name, N=n,
                    chains=chains, q_db=q, cand_capacity=cap,
                    count=int(np.asarray(nk)[0]), bitwise=same,
                    compile_s=f"{comp_s:.3f}", run_s=f"{run_s:.4f}")
                check(same, f"z_candidates != ref at {prob.name}, "
                            f"{chains} chains, q={q}")
            # bright-GLM: distinct bright ids, three quarters of C valid.
            idx = jax.vmap(lambda k: jax.random.permutation(k, n)[:cap])(
                jax.random.split(ks[2], chains)).astype(jnp.int32)
            nb = jnp.full((chains,), 3 * cap // 4, jnp.int32)
            theta = 0.1 * jax.random.normal(
                ks[3], (chains,) + tuple(model.theta_shape))
            per_chain = (None, 0, 0, 0)

            def fused(d, i, b, th):
                return bright_glm(d.x_rows, d.t, d.xi, i, b, th,
                                  family=fam, **kw)

            def ref(d, i, b, th):
                dl, c = bright_glm_ref(d.x, d.t, d.xi, i,
                                       jnp.arange(cap) < b, th, family=fam,
                                       **kw)
                return dl, jnp.sum(c)

            (dk, tk), comp_s, run_s = _aot(
                jax.vmap(fused, in_axes=per_chain), data, idx, nb, theta)
            dr, tr = jax.jit(jax.vmap(ref, in_axes=per_chain))(
                data, idx, nb, theta)
            dk, tk, dr, tr = map(np.asarray, (dk, tk, dr, tr))
            err_d = np.max(np.abs(dk - dr) / (ATOL + RTOL * np.abs(dr)))
            err_t = np.max(np.abs(tk - tr) / (ATOL + RTOL * np.abs(tr)))
            say("parity", kernel="bright_glm", problem=prob.name, N=n,
                D=prob.d, family=fam, chains=chains, capacity=cap,
                delta_err_over_tol=f"{err_d:.4f}",
                total_err_over_tol=f"{err_t:.4f}",
                compile_s=f"{comp_s:.3f}", run_s=f"{run_s:.4f}")
            check(err_d <= 1.0 and err_t <= 1.0,
                  f"bright_glm != ref at {prob.name}, {chains} chains")
        say("parity", problem=prob.name, peak_bytes_in_use=_peak())


# ---------------------------------------------------------------------------
# Phase 2: the sampler, FlyMC against regular MCMC
# ---------------------------------------------------------------------------


def _sampler_model(prob, key):
    """The problem's model for the sampler runs (see SEPARABLE)."""
    if prob.name not in SEPARABLE:
        return prob.build(key, prob.n)
    from repro.data import softmax_data
    from repro.models.bayes_glm import GLMModel

    data = softmax_data(key, n=prob.n, d=prob.d, k=3,
                        sharpness=SEPARABLE[prob.name])
    return GLMModel.softmax(data, n_classes=3, prior_scale=1.0)


def _tuned(prob, key):
    """(MAP-tuned model, θ_MAP, run key, MAP seconds) for one problem."""
    import jax

    k_data, k_map, k_run = jax.random.split(key, 3)
    model = _sampler_model(prob, k_data)
    theta_map, map_s = _timed(lambda: model.map_estimate(k_map,
                                                         steps=MAP_STEPS))
    return model.map_tuned(theta_map), theta_map, k_run, map_s


def _buffers(prob):
    """Buffers for the expected initial bright set (2·q_db·N) and a
    step's candidates (q_db·N), plus a tenth, in whole 1024-row blocks;
    an overflow grows them, exactly."""
    from repro.kernels.common import pad_to

    need = lambda rows: pad_to(int(1.1 * rows) + 1, 1024)
    return dict(capacity=need(2 * prob.q_tuned * prob.n),
                cand_capacity=need(prob.q_tuned * prob.n))


# A Mosaic kernel in lowered StableHLO: one custom_call op per line, the
# kernel's name an attribute of the op itself.
_TPU_CUSTOM_CALL = re.compile(
    r'stablehlo\.custom_call @tpu_custom_call\(.*\bkernel_name = "([^"]+)"')


def _kernels_in_chunk(alg, state, num_chains):
    """Names of the Pallas kernels compiled into the driver's chunk: the
    ``kernel_name`` of each ``tpu_custom_call`` op in its lowered text."""
    import jax
    import jax.numpy as jnp

    from repro.api import driver

    chunk = driver._make_scan_fn(alg, num_chains, CHUNK)
    keys = jax.random.split(jax.random.key(0), num_chains)
    text = chunk.lower(state, keys, jnp.int32(0), alg.data,
                       alg.stats).as_text()
    return {m.group(1) for m in map(_TPU_CUSTOM_CALL.search,
                                    text.splitlines()) if m}


def _engines(prob, key):
    """FlyMC and regular MCMC for one problem, ready to run.

    Returns (tuned model, θ_MAP, MAP seconds, {engine: (algorithm, start)},
    run) where ``run(alg, start, n)`` samples n iterations through
    ``api.sample``.
    """
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.core import flymc

    chains, iters, warm = RUNS[prob.name]
    tuned, theta_map, k_run, map_s = _tuned(prob, key)
    common = dict(kernel=prob.kernel, step_size=prob.step0,
                  adapt_target="auto", num_warmup=warm)
    bufs = _buffers(prob)
    if prob.name in DARK_START:
        bufs = dict(zip(("capacity", "cand_capacity"), DARK_START[prob.name]))
    fly = api.firefly(tuned, q_db=prob.q_tuned, backend="pallas",
                      z_backend="fused", **bufs, **common)
    reg = api.regular_mcmc(tuned, **common)
    start = dict(init_position=theta_map)
    fly_start = start
    if prob.name in DARK_START:
        def init(k, data, stats, z0):
            return flymc.init_chain_state(fly.spec, data, stats, theta_map,
                                          k, z0=z0, step_size=prob.step0)

        keys = jax.random.split(jax.random.fold_in(k_run, 1), chains)
        fly_start = dict(init_state=jax.jit(
            jax.vmap(init, in_axes=(0, None, None, None)))(
                keys, fly.data, fly.stats, jnp.zeros(prob.n, bool)))

    def run(alg, st, n):
        return api.sample(alg, k_run, n, num_chains=chains,
                          chunk_size=CHUNK, **st)

    return tuned, theta_map, map_s, {"flymc": (fly, fly_start),
                                     "regular": (reg, start)}, run


def sampler(args):
    import numpy as np

    from repro import api

    for prob, key in _problems(args.seed):
        chains, iters, warm = RUNS[prob.name]
        tuned, theta_map, map_s, algs, run = _engines(prob, key)
        out = {}
        for tag, (alg, start) in algs.items():
            _, first_s = _timed(lambda: run(alg, start, CHUNK).theta)
            trace, run_s = _timed(lambda: run(alg, start, iters))
            th = np.asarray(trace.theta)
            q = np.asarray(trace.stats.lik_queries)[:, warm:].mean()
            nb = np.asarray(trace.stats.n_bright)[:, warm:]
            say("sampler", problem=prob.name, engine=tag, N=prob.n,
                D=prob.d, theta=tuple(tuned.theta_shape), kernel=prob.kernel,
                chains=chains, iters=iters, map_s=f"{map_s:.2f}",
                compile_s=f"{max(first_s - run_s * CHUNK / iters, 0):.2f}",
                iters_per_s=f"{iters / run_s:.1f}",
                queries_per_iter=f"{q:.1f}", mean_n_bright=f"{nb.mean():.1f}",
                capacity=getattr(trace.algorithm.spec, "capacity", "-"),
                peak_bytes_in_use=_peak())
            check(bool(np.all(np.isfinite(th))),
                  f"{prob.name}/{tag}: non-finite θ")
            if tag == "flymc":
                check(0 < nb.mean() < prob.n,
                      f"{prob.name}: mean n_bright {nb.mean()} not in (0, N)")
                check(q < prob.n / 4,
                      f"{prob.name}: {q:.0f} queries/iter, not below N/4")
                found = _kernels_in_chunk(trace.algorithm, trace.final_state,
                                          chains)
                say("sampler", problem=prob.name,
                    tpu_custom_call=",".join(sorted(found)) or "none")
                check(found == {"bright_glm", "z_candidates"},
                      f"{prob.name}: chunk compiles {found or 'no'} kernels")
            out[tag] = trace
        _agree("sampler", prob.name,
               _posterior(_identified(out["flymc"].theta), warm),
               _posterior(_identified(out["regular"].theta), warm))

        if prob.kernel == "rwmh":
            # Capacity 128 is below the initial bright set (2·q_db·N ≈ 244
            # rows per chain), so init doubles both buffers once or twice,
            # to at most 512/128; a step proposes q_db·N ≈ 122 ± 11
            # candidates per chain, so some chain-step of the first chunk
            # overflows 128 and the chunk re-runs at doubled buffers.
            from repro.api import driver

            small = api.firefly(
                tuned, capacity=128, cand_capacity=32, q_db=prob.q_tuned,
                backend="pallas", z_backend="fused", kernel=prob.kernel,
                step_size=prob.step0, adapt_target="auto", num_warmup=warm)
            before = set(driver._JIT_CACHE)
            trace = run(small, algs["flymc"][1], iters)
            # One chunk executable per capacity a chunk ran at: two or more
            # means a chunk overflowed and was re-run at doubled buffers.
            caps = sorted(k[6] for k in set(driver._JIT_CACHE) - before
                          if k[0] == "scan")
            same = bool(np.array_equal(np.asarray(trace.theta),
                                       np.asarray(out["flymc"].theta)))
            say("overflow", problem=prob.name, start_capacity=128,
                start_cand_capacity=32,
                chunk_capacities=";".join(f"{a}/{b}" for a, b in caps),
                bitwise_vs_ample=same)
            check(len(caps) >= 2, "no chunk overflowed and re-ran")
            check(same, "overflow re-run changed the trajectory")


# ---------------------------------------------------------------------------
# Phase 3: the service
# ---------------------------------------------------------------------------


def serve(args):
    import jax
    import numpy as np

    from benchmarks.table1 import PROBLEMS
    from repro.serve import Job, Service, TerminationPolicy

    prob, samples = PROBLEMS[0], 200
    keys = jax.random.split(jax.random.key(args.seed + 1), 4)
    jobs = [
        Job(job_id=f"logistic-{i}", family="logistic",
            data=prob.build(k, prob.n).data, seed=i, num_chains=1,
            kernel=prob.kernel, step_size=prob.step0, q_db=prob.q_untuned,
            capacity=2048, cand_capacity=2048, backend="pallas",
            z_backend="fused", num_warmup=samples // 4,
            policy=TerminationPolicy(max_samples=samples))
        for i, k in enumerate(keys)
    ]
    chunk = 50
    svc = Service(slot_budget=4, chunk_size=chunk)
    for job in jobs:
        svc.submit(job)
    # The first round compiles the group's chunk; the rest only run.
    _, first_s = _timed(svc.step)
    results, rest_s = _timed(lambda: svc.run(max_steps=100))
    per_round = rest_s / max(samples // chunk - 1, 1)
    reasons = {j: r.reason for j, r in results.items()}
    traces = [r.results["trace"] for r in results.values()]
    finite = all(bool(np.all(np.isfinite(np.asarray(t["theta"]))))
                 for t in traces)
    stat = lambda f: np.mean([np.asarray(getattr(t["stats"], f)).mean()
                              for t in traces])
    say("serve", jobs=len(jobs), N=prob.n, D=prob.d, samples=samples,
        chunk=chunk, compile_s=f"{max(first_s - per_round, 0):.2f}",
        samples_per_s=f"{len(jobs) * samples / (first_s + rest_s):.1f}",
        queries_per_iter=f"{stat('lik_queries'):.1f}",
        mean_n_bright=f"{stat('n_bright'):.1f}", faults=len(svc.faults),
        reasons=",".join(sorted(set(reasons.values()))), finite=finite,
        peak_bytes_in_use=_peak())
    check(len(results) == len(jobs), "service did not retire every job")
    check(not svc.faults, f"service faults: {svc.faults}")
    check(set(reasons.values()) == {"max_samples"},
          f"jobs did not finish cleanly: {reasons}")
    check(finite, "non-finite θ in a served trace")


# ---------------------------------------------------------------------------
# --chips 4: row-sharded data and chain-sharded fleets
# ---------------------------------------------------------------------------


def _placement(data, devices, n):
    """Rows of ``x`` and ``x_rows`` per device, and each device's memory.

    Every device must hold N/4 rows of both. Devices 1-3 must also show
    it in their memory: a peak at least their shards' bytes and below
    what the whole dataset takes on a device (x and x_rows, N rows each,
    lane-padded). Device 0 is only reported: it also holds the one-chip
    copy the sharded run is compared with.
    """
    from repro.kernels.common import pad_to

    rows, shard_bytes = {}, {d.id: 0 for d in devices}
    for name in ("x", "x_rows"):
        shards = getattr(data, name).addressable_shards
        rows[name] = sorted((sh.device.id, sh.data.shape[0]) for sh in shards)
        for sh in shards:
            shard_bytes[sh.device.id] += sh.data.nbytes
    whole = 2 * n * pad_to(data.x.shape[1], 128) * 4
    peaks = [_peak(d) for d in devices]
    for name, per in rows.items():
        check(len(per) == 4 and all(r == n // 4 for _, r in per),
              f"{name} is not split N/4 per device: {per}")
    for d, peak in zip(devices[1:], peaks[1:]):
        check(isinstance(peak, int) and shard_bytes[d.id] <= peak < whole,
              f"device {d.id}: peak {peak} B, not within [its shards "
              f"{shard_bytes[d.id]} B, the whole dataset {whole} B)")
    return rows, peaks, shard_bytes, whole


# --chips 4: iterations of the row-sharded robust chain and of its
# one-chip twin (chains and warmup as in RUNS), and (chains, iterations,
# warmup) of the fleet, which carries the logistic run's sample count.
DIST_ITERS = 3_000
FLEET_RUN = (8, 5_000, 1_000)


def four_chips(args):
    import jax
    import numpy as np
    from jax.sharding import AxisType

    from benchmarks.table1 import PROBLEMS
    from repro import api
    from repro.core import bounds
    from repro.distributed import flymc_dist

    devices = jax.devices()[:4]
    prob_of = {p.name: (p, k) for p, k in _problems(args.seed)}

    # Row-sharded robust regression (N = 1.8M) against the sampler phase's
    # one-chip FlyMC run: the same posterior, so the means are held to
    # Z_BOUND combined MCSEs, and the placement is checked. The sharded
    # chain draws its initial bright set per shard (2·q_db of the rows,
    # hence buffers of a quarter of _buffers); the twin starts all dark
    # (DARK_START). Warmup covers both starts.
    prob, key = prob_of[PROBLEMS[2].name]
    chains, _, warm = RUNS[prob.name]
    tuned, theta_map, _, algs, run = _engines(prob, key)

    def compile_then_run(alg, start, path):
        # Each stage prints as it ends, so a run cut short shows how far
        # it got and what each stage took.
        say("four_chips", path=path, stage="first chunk", iters=CHUNK)
        _, first = _timed(lambda: run(alg, start, CHUNK).theta)
        say("four_chips", path=path, stage="run", iters=DIST_ITERS,
            first_chunk_s=f"{first:.2f}")
        trace, sec = _timed(lambda: run(alg, start, DIST_ITERS))
        say("four_chips", path=path, stage="done", run_s=f"{sec:.2f}")
        return trace, sec, max(first - sec * CHUNK / DIST_ITERS, 0.0)

    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,),
                         devices=devices)
    # The twin's data (x_rows built by api.firefly): sharded, not copied.
    data = flymc_dist.shard_data(
        bounds.with_gather_layout(algs["flymc"][0].data), mesh)
    # dist_algorithm takes the FlyMCSpec fields only: no step size (the
    # default slice width) and no "auto" adaptation target (slice sampling
    # adapts nothing).
    dist = flymc_dist.dist_algorithm(
        tuned.bound, tuned.log_prior, mesh, data, kernel=prob.kernel,
        num_warmup=warm, q_db=prob.q_tuned, backend="pallas",
        z_backend="fused", **{k: v // 4 for k, v in _buffers(prob).items()})
    say("four_chips", path="dist_algorithm", stage="rows sharded",
        peak_bytes_in_use_per_device=[_peak(d) for d in devices])
    t_dist, s_dist, c_dist = compile_then_run(
        dist, dict(init_position=theta_map), "dist_algorithm")
    rows, peaks, shard_bytes, whole = _placement(data, devices, prob.n)
    t_one, s_one, c_one = compile_then_run(*algs["flymc"], "one chip")
    nb = np.asarray(t_dist.stats.n_bright)[:, warm:]
    q = np.asarray(t_dist.stats.lik_queries)[:, warm:].mean()
    say("four_chips", path="dist_algorithm", problem=prob.name, N=prob.n,
        D=prob.d, shards=4, chains=chains, iters=DIST_ITERS,
        x_rows_per_device=rows["x"], gather_rows_per_device=rows["x_rows"],
        shard_bytes_per_device=[shard_bytes[d.id] for d in devices],
        whole_dataset_bytes=whole,
        peak_bytes_in_use_per_device=peaks,
        iters_per_s_4chips=f"{DIST_ITERS / s_dist:.1f}",
        iters_per_s_1chip=f"{DIST_ITERS / s_one:.1f}",
        compile_s_4chips=f"{c_dist:.2f}", compile_s_1chip=f"{c_one:.2f}",
        queries_per_iter=f"{q:.1f}", mean_n_bright=f"{nb.mean():.1f}")
    check(bool(np.all(np.isfinite(np.asarray(t_dist.theta)))),
          "dist_algorithm: non-finite θ")
    check(0 < nb.mean() < prob.n and q < prob.n / 4,
          f"dist_algorithm: mean n_bright {nb.mean()}, {q:.0f} queries/iter")
    _agree("four_chips", f"{prob.name} rows 4-way vs 1 chip",
           _posterior(t_dist.theta, warm), _posterior(t_one.theta, warm))

    # Chain-sharded fleet: 8 chains on 4 chips against the same 8 chains
    # batched on one chip. Same keys, same law; the per-chain programs
    # differ only in batch size, which may change the rounding of the
    # jnp-side matmuls (the collapsed bound θᵀQθ) and so flip an accept
    # decision. So each chain's first differing iteration is reported, and
    # the whole run is held to the posterior agreement of every other
    # comparison here.
    prob, key = prob_of[PROBLEMS[0].name]
    chains, iters, warm = FLEET_RUN
    tuned, theta_map, k_run, _ = _tuned(prob, key)
    alg = api.firefly(tuned, kernel=prob.kernel, q_db=prob.q_tuned,
                      step_size=prob.step0, adapt_target="auto",
                      num_warmup=warm, backend="pallas", z_backend="fused",
                      **_buffers(prob))
    fleet = flymc_dist.chain_fleet(
        alg, jax.make_mesh((4,), ("chains",), devices=devices))
    go = lambda a: api.sample(a, k_run, iters, num_chains=chains,
                              chunk_size=CHUNK, init_position=theta_map)
    say("four_chips", path="chain_fleet", stage="one chip", iters=iters)
    t_local, s_local = _timed(lambda: go(alg))
    say("four_chips", path="chain_fleet", stage="fleet",
        first_call_s_1chip=f"{s_local:.2f}")
    t_fleet, s_fleet = _timed(lambda: go(fleet))
    a, b = np.asarray(t_local.theta), np.asarray(t_fleet.theta)
    differs = np.any(a != b, axis=2)  # (chains, iterations)
    first = [int(np.argmax(r)) if r.any() else -1 for r in differs]
    say("four_chips", path="chain_fleet", problem=prob.name, chains=chains,
        devices=4, iters=iters, bitwise_vs_one_chip=not differs.any(),
        first_differing_iteration_per_chain=first,
        first_call_s_4chips=f"{s_fleet:.2f}",
        first_call_s_1chip=f"{s_local:.2f}",
        peak_bytes_in_use_per_device=[_peak(d) for d in devices])
    check(bool(np.all(np.isfinite(b))), "chain_fleet: non-finite θ")
    check(bool(np.allclose(a[:, 0], b[:, 0], rtol=RTOL, atol=ATOL)),
          "chain_fleet's first step differs from one chip beyond rounding")
    _agree("four_chips", f"{prob.name} fleet vs 1 chip",
           _posterior(b, warm), _posterior(a, warm))


if __name__ == "__main__":
    sys.exit(main())
