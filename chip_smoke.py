"""Smoke run of the main path on a TPU: the quickest proof that the system
still starts on the chip.

    python chip_smoke.py               # one chip: qsgd mean, swarm, serving
    python chip_smoke.py --four-chips  # four chips: MeshPlan lane sharding only

One chip.  protocol-125m at its published width (162,417,408 parameters,
d_model 768, 12 layers, vocab 32000; random weights from seed 0, synthetic
data from ``data/pipeline.py``) goes through the normal entry points:

- the round's masked mean over a qsgd wire at that parameter count, which
  must take the Pallas decode-accumulate kernel and agree with the plain
  decode;
- the swarm round — the builders of ``examples/swarm_byzantine_training.py``
  with the registry scenario ``derailment_stress`` at ``NODES`` nodes (the
  most whose round fits one chip's 16 GB), on the batched engine.  The
  round must resolve to the fused path, its compiled program must hold
  Pallas TPU kernels (``tpu_custom_call``), the loss must stay finite,
  audits must slash adversaries and only adversaries, and the ownership
  ledger must conserve;
- the serving engine of ``launch/serve.py --driver engine`` on the trained
  params: every request served, availability 1.0.

Four chips.  A lane grid of the same scenario (reduced LM, 16 lanes per
chip) runs as one ``run_campaign`` program lane-sharded over all four chips
by a ``MeshPlan``, and again unsharded on one chip — per chip's block of
lanes and as one program over all of them — held to the lane-axis contract
of ``docs/scaling.md``.

Every check raises on failure, so a failed phase fails the script; nothing
is caught and skipped.  Without a TPU it exits non-zero before any phase.
The times printed along the way are smoke readings, not measurements.  The
last stdout line, printed only when everything passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SCENARIO = "derailment_stress"
NODES = 8            # the round at 9+ nodes exceeds one v5e chip's HBM
ROUNDS = 6           # audits catch this roster's 3 adversaries by round 4
REQUESTS, PROMPT_LEN, MAX_NEW, SLOTS = 8, 16, 16, 4
LANES, LANE_ROUNDS, LANE_NODES = 64, 4, 5  # 16 lanes per chip on four


def _smoke(label: str, value) -> None:
    print(f"smoke reading: {label} = {value}", flush=True)


def assert_tpu_kernels(hlo_text: str) -> None:
    """The compiled round must run its kernels as Mosaic custom calls."""
    if "tpu_custom_call" not in hlo_text:
        raise AssertionError("compiled round holds no tpu_custom_call: the "
                             "fused kernels did not take the Pallas path")


def swarm_phase(full: bool = True, n_nodes: int = NODES,
                rounds: int = ROUNDS):
    """Train ``rounds`` rounds; returns (model, params, vocab_size)."""
    import jax
    import swarm_byzantine_training as ex

    cfg, model = ex.build_lm(full)
    nodes, swarm_cfg = ex.build_roster(SCENARIO, rounds, n_nodes)
    print(f"swarm: {cfg.name} params={cfg.param_count():,} "
          f"scenario={SCENARIO} nodes k={len(nodes)}", flush=True)
    swarm, eval_fn = ex.build_swarm(cfg, model, nodes, swarm_cfg)
    if not swarm.fused:
        raise AssertionError("the round did not resolve to the fused path")

    t0 = time.perf_counter()
    compiled = swarm.lower_step(0).compile()
    _smoke("swarm round compile s", time.perf_counter() - t0)
    assert_tpu_kernels(compiled.as_text())
    eval_loss = jax.jit(eval_fn)
    loss0 = float(eval_loss(swarm.params))

    swarm.step(0)                     # round 0 runs the program compiled above
    t0 = time.perf_counter()
    for r in range(1, rounds):
        swarm.step(r)                 # returns host values: waits for the chip
    dt = time.perf_counter() - t0
    _smoke("swarm rounds/s", (rounds - 1) / dt)
    loss = float(eval_loss(swarm.params))
    print(f"swarm: eval loss {loss0} -> {loss} after {rounds} rounds; "
          f"slashed {sorted(swarm.slashed)}", flush=True)
    if not (math.isfinite(loss0) and math.isfinite(loss)):
        raise AssertionError(f"non-finite loss: {loss0} -> {loss}")
    adversaries = {n.node_id for n in nodes if n.byzantine}
    if not swarm.slashed or not swarm.slashed <= adversaries:
        raise AssertionError(f"audits slashed {sorted(swarm.slashed)}; "
                             f"adversaries are {sorted(adversaries)}")
    if not swarm.ledger.check_conservation():
        raise AssertionError("ownership ledger does not conserve")
    return model, swarm.params, cfg.vocab_size


def qsgd_phase(n_nodes: int = NODES, full: bool = True) -> None:
    """The round's masked mean over a qsgd wire at full width.

    ``masked_mean_fused`` — what the round calls for the mean aggregator
    over a qsgd wire — on ``n_nodes`` payloads of protocol-125m's
    parameter count (the showcase wire: levels 127, buckets of 512), with
    the kernel choice left to ``repro.kernels``: on a TPU the Pallas
    decode-accumulate kernel.  Checked against the plain decode of
    ``kernels/qsgd_decode/ref.py``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import swarm_byzantine_training as ex
    from repro.kernels.masked_agg.ops import masked_mean_fused
    from repro.kernels.qsgd_decode import ops as qdec
    from repro.kernels.qsgd_decode.ref import decode_accumulate_ref

    cfg, _ = ex.build_lm(full)
    d = cfg.param_count()
    ckw = ex.showcase_roster(ROUNDS)[1].compression_kwargs
    encode = jax.jit(lambda k: qdec.wire_encode(
        k, jax.random.normal(k, (d,)), **ckw))
    keys = jax.random.split(jax.random.PRNGKey(2), n_nodes)
    payload = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[encode(k) for k in keys])
    mask = jnp.arange(n_nodes) != 1              # one node churned out

    t0 = time.perf_counter()
    fused = jax.jit(masked_mean_fused).lower(payload, mask).compile()
    _smoke("qsgd fused mean compile s", time.perf_counter() - t0)
    assert_tpu_kernels(fused.as_text())
    got = jax.block_until_ready(fused(payload, mask))
    t0 = time.perf_counter()
    jax.block_until_ready(fused(payload, mask))
    _smoke("qsgd fused mean s", time.perf_counter() - t0)
    want = jax.jit(lambda p, m: decode_accumulate_ref(
        p, m.astype(jnp.float32)) / jnp.sum(m))(payload, mask)
    got, want = np.asarray(got), np.asarray(want)
    print(f"qsgd: nodes={n_nodes} D={d:,} levels={ckw['levels']} "
          f"bucket={ckw['bucket_size']} max |fused - ref| "
          f"{float(np.max(np.abs(got - want)))}", flush=True)
    if got.shape != (d,) or not np.all(np.isfinite(got)) \
            or not np.any(got):
        raise AssertionError(f"fused mean: shape {got.shape}, finite "
                             f"{np.all(np.isfinite(got))}")
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def serving_phase(model, params, vocab_size: int) -> None:
    import jax
    import numpy as np
    from repro.launch.serve import engine_for

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (REQUESTS, PROMPT_LEN), 0, vocab_size)
    engine, lane = engine_for(model, prompts, slots=SLOTS, max_new=MAX_NEW)
    t0 = time.perf_counter()
    engine.run(params, lane)          # compiles, then serves the queue
    _smoke("serving compile+first run s", time.perf_counter() - t0)
    res = engine.run(params, lane)
    _smoke("serving tok/s", res.tok_per_s)
    served = int(np.sum(res.done))
    print(f"serving: requests={REQUESTS} served={served} "
          f"tokens={res.tokens_served} availability {res.availability}",
          flush=True)
    toks = np.asarray(res.tokens)
    if served != REQUESTS or res.availability != 1.0:
        raise AssertionError(f"served {served}/{REQUESTS}, availability "
                             f"{res.availability}")
    if res.tokens_served != REQUESTS * MAX_NEW or toks.min() < 0 \
            or toks.max() >= vocab_size:
        raise AssertionError(f"bad output tokens: {res.tokens_served} "
                             f"served, range [{toks.min()}, {toks.max()}]")


def _lane_campaign(plan=None, lanes_slice=None):
    """The four-chip phase's campaign: LANES seeds of the scenario on the
    reduced LM, CenteredClip + audits.  Returns run_campaign's output."""
    import jax
    from common import small_lm_problem
    import swarm_byzantine_training as ex
    from repro.core.swarm import lane_for_nodes, run_campaign, stack_lanes

    loss_fn, params, data_fn, eval_fn, opt = small_lm_problem()
    nodes, cfg = ex.build_roster(SCENARIO, LANE_ROUNDS, LANE_NODES)
    lanes = stack_lanes([lane_for_nodes(nodes, dataclasses.replace(cfg, seed=s))
                         for s in range(LANES)])
    if lanes_slice is not None:
        lanes = jax.tree.map(lambda x: x[lanes_slice], lanes)
    return run_campaign(loss_fn, params, opt, data_fn, lanes,
                        rounds=LANE_ROUNDS, aggregator=cfg.aggregator,
                        agg_kwargs=cfg.agg_kwargs, verify=True,
                        eval_fn=eval_fn, plan=plan)


def _assert_bitequal(a, b, what: str) -> None:
    import jax
    import numpy as np
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if not np.array_equal(np.asarray(la), np.asarray(lb)):
            raise AssertionError(f"{what} differs bitwise")


def _bf16_ulp(x):
    """One bf16 ULP at the magnitude of each element of ``x`` (float64)."""
    import numpy as np
    m = np.maximum(np.abs(x), np.finfo(np.float32).tiny)
    return np.exp2(np.floor(np.log2(m)) - 7)


def _assert_within_bf16_ulp(a, b, what: str) -> tuple[int, int]:
    """Every float element of ``a`` within one bf16 ULP of ``b`` at the
    magnitude of each of the two (both directions), every other leaf
    equal.  Returns (elements that moved, elements compared)."""
    import jax
    import numpy as np
    moved = total = 0
    for (path, la), lb in zip(jax.tree_util.tree_leaves_with_path(a),
                              jax.tree.leaves(b)):
        la, lb = np.asarray(la), np.asarray(lb)
        where = what + jax.tree_util.keystr(path)
        if not np.issubdtype(la.dtype, np.floating):
            if not np.array_equal(la, lb):
                raise AssertionError(f"{where} differs")
            continue
        la, lb = la.astype(np.float64), lb.astype(np.float64)
        gap = np.abs(la - lb)
        if not np.all(gap <= np.minimum(_bf16_ulp(la), _bf16_ulp(lb))):
            raise AssertionError(f"{where}: max gap {gap.max()} is more "
                                 f"than one bf16 ULP")
        moved += int(np.count_nonzero(gap))
        total += gap.size
    return moved, total


def four_chip_phase() -> None:
    """Lane sharding over all chips vs the same lanes on one chip.

    The contract (docs/scaling.md): sharding the lane axis changes no bit
    of the program each device runs, so the sharded campaign equals the
    unsharded engine run on each device's block of lanes — params, opt
    state and every RoundRecord field bit-equal, the final eval allclose.
    Against one unsharded program over all lanes every counter is equal
    and every float within one bf16 ULP.  The lane count is such that
    each device holds 16 lanes: a (lanes, D) array then fills whole (8,
    128) tiles, and the v5e reduces it in the same order as the wider
    program (below 8 lanes per device it does not; docs/scaling.md)."""
    import jax
    import numpy as np
    from repro.core.placement import MeshPlan

    plan = MeshPlan.for_lanes(LANES)
    if plan.n_devices != 4 or plan.lane_devices != 4:
        raise AssertionError(f"plan spans {plan.n_devices} devices, "
                             f"lane axis {plan.lane_devices}; want 4")
    t0 = time.perf_counter()
    st, rec, fin = _lane_campaign(plan)
    jax.block_until_ready(fin)
    _smoke("sharded campaign compile+run s", time.perf_counter() - t0)
    for leaf in jax.tree.leaves(st.params):
        devs = [s.device for s in leaf.addressable_shards]
        if len(set(devs)) != 4 or len(devs) != 4:
            raise AssertionError(f"output leaf has shards on {devs}")

    per = LANES // plan.lane_devices
    blocks = [_lane_campaign(lanes_slice=slice(i, i + per))
              for i in range(0, LANES, per)]
    cat = lambda *xs: np.concatenate([np.asarray(x) for x in xs])
    st_b, rec_b, fin_b = jax.tree.map(cat, *blocks)
    _assert_bitequal(st.params, st_b.params, "params")
    _assert_bitequal(st.opt_state, st_b.opt_state, "opt state")
    for f in rec._fields:
        _assert_bitequal(getattr(rec, f), getattr(rec_b, f),
                         f"RoundRecord.{f}")
    np.testing.assert_allclose(np.asarray(fin), fin_b, rtol=1e-6)

    st_w, rec_w, fin_w = _lane_campaign()
    moved, total = _assert_within_bf16_ulp((st, rec, fin),
                                           (st_w, rec_w, fin_w), "")
    print(f"four chips: {LANES} lanes over {plan.lane_devices} devices "
          f"bit-equal to {LANES // per} one-chip blocks of {per} lanes; "
          f"within one bf16 ULP of one {LANES}-lane program ({moved} of "
          f"{total} float elements differ), counters equal; slashed per lane "
          f"{np.asarray(st.slashed).sum(axis=1).tolist()}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip MeshPlan campaign phase")
    args = ap.parse_args(argv)
    for sub in ("src", "examples"):
        sys.path.insert(0, os.path.join(ROOT, sub))

    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU here (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    if args.four_chips:
        if len(devices) != 4:
            print(f"chip_smoke: --four-chips needs 4 chips, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        four_chip_phase()
    else:
        qsgd_phase()
        model, params, vocab = swarm_phase()
        serving_phase(model, params, vocab)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
