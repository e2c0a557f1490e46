"""The round's and the serving step's stage scopes, read back from the
compiled programs: every variant of the round (dense, fused CenteredClip,
fused qsgd wire, decentralized, async, economy) and the serving step put
their operations under the stages they run, and leave almost none outside.
Tiny shapes on the CPU; the scopes are op metadata, whatever the size."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import programs as P
from repro.analysis.stages import stage_map, stage_of
from repro.core import economy, serving, swarm
from repro.core.economy import EconomyConfig
from repro.core.swarm import SwarmConfig
from repro.core.verification import VerificationConfig
from repro.optim.optimizer import SGD

ROUND = {"swarm.grad", "swarm.flatten", "swarm.corrupt", "swarm.audit",
         "swarm.aggregate", "swarm.update", "swarm.record"}
#: instructions that compute nothing on the device, and those that enclose
#: other instructions (their time is their body's)
FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
ENCLOSING = {"while", "conditional", "call"}


def _opcode(line: str) -> str:
    rhs = line.split(" = ", 1)[1]
    if rhs.startswith("("):                       # a tuple-shaped result
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.split(" ", 1)[1]
    return rhs.strip().split("(", 1)[0]


def unscoped_share(text: str) -> float:
    """Of the instructions the program's own code made (an ``op_name``
    under its ``jit(...)``) that compute on the device, the share in no
    stage."""
    _, stages = stage_map(text)
    counted = unscoped = 0
    for line in text.splitlines():
        if ' = ' not in line or 'op_name="jit(' not in line:
            continue
        if _opcode(line) in FREE | ENCLOSING:
            continue
        name = line.split(" = ", 1)[0].split()[-1].lstrip("%")
        counted += 1
        unscoped += stages.get(name) is None
    assert counted > 0
    return unscoped / counted


def _round_text(variant: str) -> str:
    n = 4
    fused = variant in ("fused_cc", "fused_qsgd")
    params, loss_fn, data_fn, _ = P._tiny_problem(128 if fused else 8)
    opt = SGD(lr=0.05)
    kw = dict(aggregator="centered_clip", verify=True)
    cfg = SwarmConfig(verification=VerificationConfig(p_check=0.5))
    roster = P._roster(n, attack=True)
    state = None
    if variant == "fused_cc":
        kw.update(fused=True)
    elif variant == "fused_qsgd":
        kw.update(fused=True, compression_kind="qsgd",
                  compression_kwargs={"levels": 64})
    elif variant == "decentralized":
        kw.update(decentralized=True)
        cfg = replace(cfg, topology="ring")
        state = swarm.init_decentralized_state(params, opt, n)
    elif variant == "async":
        kw.update(staleness_bound=2)
        cfg = replace(cfg, staleness_bound=2)
        roster = [replace(nd, delay=i % 3) for i, nd in enumerate(roster)]
        state = swarm.init_state(params, opt, n, staleness_bound=2)
    elif variant == "economy":
        kw.update(aggregator="mean")
        cfg = replace(cfg, economy=EconomyConfig(adaptive=True))
    round_fn = swarm.make_round_fn(loss_fn, opt, params, n, **kw)
    lane = swarm.lane_for_nodes(roster, cfg)
    if variant == "economy":
        state = swarm.init_state(params, opt, n,
                                 econ=economy.init_econ_state(lane.econ, n))
    state = state or swarm.init_state(params, opt, n)
    batches = P._batch_fn(data_fn, n)(0)
    return jax.jit(round_fn).lower(lane, state, jnp.asarray(0, jnp.int32),
                                   batches).compile().as_text()


@pytest.mark.parametrize("variant, extra", [
    ("dense", set()),
    ("fused_cc", set()),
    ("fused_qsgd", {"swarm.wire"}),
    ("decentralized", {"swarm.gossip"}),
    ("async", set()),
    ("economy", set()),
])
def test_round_stages_cover_the_program(variant, extra):
    text = _round_text(variant)
    module, stages = stage_map(text)
    assert module == "jit_round_fn"
    assert set(stages.values()) - {None} == ROUND | extra
    assert unscoped_share(text) < 0.05


def _serve_text() -> str:
    from repro.configs import get_config
    from repro.models.model import build_model

    cfg = get_config("protocol-125m").reduced(
        num_layers=1, d_model=32, num_heads=2, head_dim=16, d_ff=64,
        vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (6, 6), 0,
                                 cfg.vocab_size)
    scfg = serving.ServingConfig(slots=3, max_new=4, steps=20)
    engine = serving.ServingEngine(model, scfg, prompts)
    lane = serving.build_lane(
        n_requests=6, prompt_lens=[6, 4, 5, 6, 3, 4], max_new=4, steps=20,
        n_nodes=4, balances=[8.0, 8.0, 1.0], load=1.0)
    program = engine.program(has_custody=False, vmapped=False)
    return program.lower(params, prompts, lane).compile().as_text()


def test_serve_stages_cover_the_step():
    text = _serve_text()
    _, stages = stage_map(text)
    assert set(stages.values()) - {None} == {
        "serve.admit", "serve.decode", "serve.cache_write", "serve.retire"}
    assert unscoped_share(text) < 0.05


@pytest.mark.parametrize("op_name, stage", [
    ("jit(round_fn)/swarm.grad/vmap(transpose(jvp()))/dot_general",
     "swarm.grad"),
    ("jit(f)/transpose(jvp(swarm.update))/cond/branch_1_fun/mul",
     "swarm.update"),
    ("jit(run)/while/body/serve.decode/vmap(decode_step)/swarm.grad/x",
     "serve.decode"),
    ("jit(run)/while/body/closed_call", None),
    ("jit(f)/myswarm.grad/add", None),
])
def test_stage_of_reads_the_first_stage(op_name, stage):
    assert stage_of(op_name) == stage


def test_stage_map_reads_instruction_names():
    text = "\n".join([
        "HloModule jit_run, is_scheduled=true",
        "ENTRY %main.3 (p: f32[8]) -> f32[8] {",
        '  %p = f32[8]{0} parameter(0), metadata={op_name="p"}',
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_type="mul" op_name="jit(run)/serve.admit/mul" '
        'source_file="x.py" source_line=3}',
        '  ROOT %add.2 = f32[8]{0} add(%fusion.1, %fusion.1), '
        'metadata={op_name="jit(run)/serve.decode/add"}',
        "}"])
    assert stage_map(text) == ("jit_run", {"p": "serve.admit",
                                           "fusion.1": "serve.admit",
                                           "add.2": "serve.decode"})
    assert np.isclose(unscoped_share(text), 0.0)


def test_stage_map_places_what_the_compiler_made():
    """Instructions with no stage of their own: a fusion by its fused
    instructions, a copy by the value it moves, a loop body's copy by its
    loop, a buffer by the instruction that reads it."""
    meta = 'metadata={op_name="jit(f)/%s/x"}'
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "%fused_computation.1 (param_0: f32[8]) -> f32[8] {",
        "  %param_0 = f32[8]{0} parameter(0)",
        "  %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), "
        + meta % "swarm.corrupt",
        "  ROOT %add.1 = f32[8]{0} add(%multiply.1, %param_0), "
        + meta % "swarm.corrupt",
        "}",
        "%body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {",
        "  %arg = (s32[], f32[8]{0}) parameter(0)",
        "  %gte.3 = f32[8]{0} get-tuple-element(%arg), index=1",
        "  %copy.4 = f32[8]{0} copy(%gte.3)",
        "  ROOT %tuple.5 = (s32[], f32[8]{0}) tuple(%gte.3, %copy.4)",
        "}",
        "ENTRY %main.6 (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, "
        "calls=%fused_computation.1",
        "  %copy.8 = f32[8]{0} copy(%fusion.7)",
        "  %while.9 = (s32[], f32[8]{0}) while(%copy.8), "
        "condition=%cond.0, body=%body.2, " + meta % "swarm.grad",
        "  %constant.10 = f32[8]{0} constant(0)",
        "  ROOT %dynamic-update-slice.11 = f32[8]{0} dynamic-update-slice("
        "%constant.10, %p), " + meta % "swarm.flatten",
        "}"])
    _, stages = stage_map(text)
    assert stages["fusion.7"] == stages["copy.8"] == "swarm.corrupt"
    assert stages["copy.4"] == stages["gte.3"] == "swarm.grad"
    assert stages["constant.10"] == "swarm.flatten"
