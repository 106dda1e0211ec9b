"""The program's own names: ``obs.span`` host spans on the profiler's
clock, ``jax.named_scope`` paths on device operations, and the names of
the compiled programs. Counts and structure only — a CPU run gives no
time worth asserting.

- a tiny ``Scheduler`` ticked under ``jax.profiler.trace`` and read back
  with ``ProfileData``: every span of the tick is there with its
  attributes, nested in its tick, and carries the ``rid`` it served;
- ``obs.span`` leaves the flight recorder alone unless one is attached,
  and feeds it the records the scheduler used to hand-roll;
- a tiny conf net (conv + BatchNorm + loss) and the tiny engine, lowered
  and compiled: the operations carry their layer's scope, forward and
  backward;
- the programs' names, which key the ``XLA Modules`` line of a trace.
"""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import obs
from singa_tpu.config import parse_model_config
from singa_tpu.data.loader import synthetic_arrays, write_records
from singa_tpu.graph.builder import build_net
from singa_tpu.models.transformer import TransformerConfig, init_lm
from singa_tpu.params import init_params
from singa_tpu.serve import Engine, EngineConfig, Request, Scheduler
from singa_tpu.utils import Timers

SCHED_SPANS = {
    "sched.tick": {"tick"},
    "sched.admit": {"tick", "rid", "slot"},
    "sched.prefill": {"tick", "rid", "slot", "tokens"},
    "sched.decode": {"tick"},
    "sched.draft": {"tick", "drafted"},
    "sched.dispatch": {"tick", "live"},
    "sched.pull": {"tick"},
    "sched.emit": {"tick", "emitted"},
}
#: the engine's hand-overs: their attributes, and the scheduler spans
#: that call them (a request its first token ends retires in the tick's
#: ``sched.decode``). A pass (decode, block step, verify) has no engine
#: span: ``sched.dispatch`` bounds it
ENGINE_SPANS = {
    "engine.admit": ({"slot", "blocks"}, {"sched.admit"}),
    "engine.prefill": ({"slot", "tokens", "pos0"}, {"sched.prefill"}),
    "engine.activate": ({"slot"}, {"sched.prefill"}),
    "engine.retire": ({"slot"}, {"sched.emit", "sched.decode"}),
}


class Recorder:
    """The recorder's two entry points, kept as lists."""

    def __init__(self):
        self.events, self.spans = [], []

    def event(self, kind, **payload):
        self.events.append((kind, payload))

    def record_span(self, name, t0_wall, dur, *, track="phases", steps=None):
        self.spans.append((name, track, steps))


def tiny_engine(**serving):
    cfg = TransformerConfig(
        vocab=32, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=32
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)
    return Engine(params, cfg, EngineConfig(
        slots=2, kv_block_len=8, max_prefill_chunk=4, **serving,
    ))


def serve_some(sched, n=3):
    rs = np.random.RandomState(0)
    for rid in range(n):
        sched.submit(Request(
            rid=rid, prompt=rs.randint(0, 32, size=(6 + rid,)),
            max_new_tokens=4,
        ))
    sched.serve()


def read_spans(trace_dir, lines=False):
    """-> [(name, start, end, attrs)] of the ``singa/`` events; with
    ``lines``, each with its thread line's plane and name last."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("singa/"):
                    attrs = {
                        k: v for k, v in dict(e.stats).items()
                        if not k.startswith("_")
                    }
                    out.append((
                        e.name[len("singa/"):], e.start_ns,
                        e.start_ns + e.duration_ns, attrs,
                    ) + (((plane.name, line.name),) if lines else ()))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three requests through a speculating scheduler (so that
    ``sched.draft`` runs too) under the profiler, and the trainer's
    phases beside them."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    rec = Recorder()
    sched = Scheduler(tiny_engine(spec_k=2), recorder=rec)
    timers = Timers()
    with jax.profiler.trace(trace_dir):
        serve_some(sched)
        with timers.phase("train", steps=8):
            pass
    return read_spans(trace_dir), rec, sched


@pytest.mark.parametrize("name", sorted(SCHED_SPANS))
def test_scheduler_span_is_there_with_its_attributes(traced, name):
    spans, _, sched = traced
    mine = [s for s in spans if s[0] == name]
    assert mine, name
    for _, _, _, attrs in mine:
        assert set(attrs) >= SCHED_SPANS[name], (name, attrs)
        assert 0 <= attrs["tick"] < sched.ticks
    if name == "sched.tick":
        assert [s[3]["tick"] for s in mine] == list(range(sched.ticks))


def test_spans_nest_in_their_tick(traced):
    spans, _, _ = traced
    ticks = {s[3]["tick"]: s for s in spans if s[0] == "sched.tick"}
    for name, start, end, attrs in spans:
        if name.startswith("sched.") and name != "sched.tick":
            _, t0, t1, _ = ticks[attrs["tick"]]
            assert t0 <= start and end <= t1, (name, attrs)
    # draft, dispatch and pull lie in the tick's ``sched.decode``
    decodes = [s for s in spans if s[0] == "sched.decode"]
    for name, start, end, attrs in spans:
        if name in ("sched.draft", "sched.dispatch", "sched.pull"):
            assert any(
                d[1] <= start and end <= d[2]
                and d[3]["tick"] == attrs["tick"] for d in decodes
            ), (name, attrs)


@pytest.mark.parametrize("span,event", [
    ("sched.admit", "request_admit"), ("sched.prefill", "prefill"),
])
def test_request_spans_carry_the_rid_they_served(traced, span, event):
    spans, rec, _ = traced
    served = sorted(
        (p["rid"], p["slot"]) for kind, p in rec.events if kind == event
    )
    assert served
    got = sorted(
        (a["rid"], a["slot"]) for n, _, _, a in spans
        if n == span and "stalled" not in a
    )
    assert got == served


@pytest.fixture(scope="module")
def engine_traced(tmp_path_factory):
    """One-token ticks (one request its first token ends among them) and
    block steps, one after the other under one profiler session: the
    spans with their thread lines."""
    trace_dir = str(tmp_path_factory.mktemp("engine_trace"))
    rs = np.random.RandomState(1)
    with jax.profiler.trace(trace_dir):
        sched = Scheduler(tiny_engine())
        serve_some(sched)
        sched.submit(Request(rid=3, prompt=rs.randint(0, 32, size=(5,)),
                             max_new_tokens=1))
        sched.serve()
        blocks = Scheduler(tiny_block_engine())
        for rid in range(2):
            blocks.submit(Request(
                rid=rid, prompt=rs.randint(0, 39, size=(6 + rid,)),
                max_new_tokens=6,
            ))
        blocks.serve()
    return read_spans(trace_dir, lines=True)


@pytest.mark.parametrize("name", sorted(ENGINE_SPANS))
def test_engine_span_lies_in_the_scheduler_span_that_calls_it(
    engine_traced, name
):
    attrs_wanted, callers = ENGINE_SPANS[name]
    mine = [s for s in engine_traced if s[0] == name]
    assert mine, name
    for _, start, end, attrs, line in mine:
        assert set(attrs) >= attrs_wanted, (name, attrs)
        # host scalars, never an array the annotation would wait for
        assert all(type(v) is int for v in attrs.values()), attrs
        outer = [
            s for s in engine_traced
            if s[0] in callers and s[4] == line and s[1] <= start
            and end <= s[2]
        ]
        assert outer, (name, attrs)
        if name == "engine.prefill":
            (chunk,) = outer
            assert chunk[3]["slot"] == attrs["slot"]
            assert chunk[3]["tokens"] == attrs["tokens"]
    if name == "engine.retire":
        # the request of one token retired where its first token landed
        assert {s[0] for s in engine_traced if s[0] in callers and any(
            s[1] <= r[1] and r[2] <= s[2] for r in mine
        )} == callers


def test_no_engine_span_around_a_pass(engine_traced):
    names = {s[0] for s in engine_traced if s[0].startswith("engine.")}
    assert names == set(ENGINE_SPANS)
    assert not names & {"engine.decode", "engine.block_step", "engine.verify"}
    # a chunk's positions follow each other within its slot's prompt
    chunks = [s[3] for s in engine_traced if s[0] == "engine.prefill"]
    assert any(c["pos0"] > 0 for c in chunks)


def test_trainer_phase_is_on_the_profilers_clock(traced):
    spans, _, _ = traced
    (phase,) = [s for s in spans if s[0] == "trainer.train"]
    assert phase[3]["steps"] == 8


def test_recorder_gets_the_schedulers_spans_through_obs_span(traced):
    _, rec, sched = traced
    ticks = [s for s in rec.spans if s[0] == "decode_tick"]
    assert len(ticks) == sched.decode_ticks
    assert all(track == "serving" for _, track, _ in ticks)
    assert sum(steps for _, _, steps in ticks) == sched.tokens_emitted
    requests = [s for s in rec.spans if s[0] == "request"]
    assert sorted(steps for _, _, steps in requests) == [4, 4, 4]
    assert all(track == "requests" for _, track, _ in requests)
    assert sched.full_tick_s > 0


def test_no_recorder_no_record_and_no_trace_needed():
    """Outside a profiler session, with no recorder attached, a span is
    two clock reads: the scheduler runs as before and nothing is kept."""
    sched = Scheduler(tiny_engine())
    serve_some(sched)
    assert len(sched.finished) == 3 and sched.full_tick_s > 0
    with obs.span("anything", tick=1) as sp:
        pass
    assert sp.dur >= 0 and sp.t0_wall > 0
    sp.record(None, "anything")  # no recorder: no-op
    rec = Recorder()
    sp.record(rec, "anything", track="t", steps=3)
    assert rec.spans == [("anything", "t", 3)]


def test_timers_phase_still_feeds_its_sink():
    got = []
    t = Timers(span_sink=lambda name, t0, dur, steps: got.append(
        (name, steps, t0 > 0, dur >= 0)
    ))
    with t.phase("train", steps=8):
        pass
    with t.phase("data"):
        pass
    assert got == [("train", 8, True, True), ("data", 1, True, True)]
    assert t.steps("train") == 8 and t.total("train") >= 0


def test_flight_recorder_span_goes_through_obs_span(tmp_path):
    trace_dir = str(tmp_path / "trace")
    rec = obs.FlightRecorder(str(tmp_path / "events"))
    with jax.profiler.trace(trace_dir):
        with rec.span("assemble_batch", track="feeder"):
            pass
    assert rec.recorded == 1
    assert [s[0] for s in read_spans(trace_dir)] == ["feeder.assemble_batch"]


# ---------------------------------------------------------------------
# device names
# ---------------------------------------------------------------------


def instructions(text):
    """-> [(opcode, op_name)] of a compiled module's text."""
    out = []
    for line in text.splitlines():
        m = re.search(r"= \S+ ([a-z\-]+)\(", line)
        n = re.search(r'op_name="([^"]+)"', line)
        if m and n:
            out.append((m.group(1), n.group(1)))
    return out


@pytest.fixture(scope="module")
def conv_net_text(tmp_path_factory):
    """A conv + BatchNorm + loss conf net, forward and backward in one
    compiled program."""
    shard = str(tmp_path_factory.mktemp("shard") / "shard")
    write_records(shard, *synthetic_arrays(16, seed=4))
    net = build_net(parse_model_config(f"""
name: "names"
train_steps: 1
updater {{ base_learning_rate: 0.1 param_type: "Param" }}
neuralnet {{
  layer {{ name: "data" type: "kShardData"
          data_param {{ path: "{shard}" batchsize: 8 }} }}
  layer {{ name: "mnist" type: "kMnistImage" srclayers: "data"
          mnist_param {{ norm_a: 255 norm_b: 0 }} }}
  layer {{ name: "label" type: "kLabel" srclayers: "data" }}
  layer {{ name: "c1" type: "kConvolution" srclayers: "mnist"
          convolution_param {{ num_filters: 4 kernel: 3 stride: 1 }}
          param {{ name: "weight" init_method: "kUniformSqrtFanIn" }}
          param {{ name: "bias" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "bn1" type: "kBatchNorm" srclayers: "c1"
          param {{ name: "gamma" init_method: "kConstant" value: 1 }}
          param {{ name: "beta" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "relu1" type: "kReLU" srclayers: "bn1" }}
  layer {{ name: "c2" type: "kConvolution" srclayers: "relu1"
          convolution_param {{ num_filters: 4 kernel: 3 stride: 2 }}
          param {{ name: "weight" init_method: "kUniformSqrtFanIn" }}
          param {{ name: "bias" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "bn2" type: "kBatchNorm" srclayers: "c2"
          param {{ name: "gamma" init_method: "kConstant" value: 1 }}
          param {{ name: "beta" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "fc" type: "kInnerProduct" srclayers: "bn2"
          inner_product_param {{ num_output: 10 }}
          param {{ name: "w" init_method: "kUniformSqrtFanIn" }}
          param {{ name: "b" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "loss" type: "kSoftmaxLoss" srclayers: "fc" srclayers: "label"
          softmaxloss_param {{ topk: 1 }} }}
}}
"""), "kTrain")
    params = init_params(jax.random.PRNGKey(0), net.param_specs())
    (dl,) = net.datalayers
    batch = {"data": {"image": jnp.asarray(dl.images[:8]),
                      "label": jnp.asarray(dl.labels[:8])}}

    def loss(p):
        return net.forward(p, batch, training=True)[0]

    return jax.jit(jax.grad(loss)).lower(params).compile().as_text()


def test_every_convolution_carries_its_layers_scope(conv_net_text):
    # by the primitive at the path's end: XLA:CPU rewrites a backward
    # convolution into other opcodes, its ``op_name`` stays
    convs = {
        n for _, n in instructions(conv_net_text)
        if n.endswith("/conv_general_dilated")
    }
    assert any(op == "convolution" for op, _ in instructions(conv_net_text))
    for name in convs:
        assert re.search(r"kConvolution\.c[12]\)*/", name), name
    for layer in ("c1", "c2"):
        assert f"jit(loss)/jvp(kConvolution.{layer})/conv_general_dilated" in convs
        assert (
            f"jit(loss)/transpose(jvp(kConvolution.{layer}))"
            "/conv_general_dilated"
        ) in convs


@pytest.mark.parametrize("layer", ["bn1", "bn2"])
def test_batchnorm_reductions_carry_its_scope_both_ways(conv_net_text, layer):
    """BatchNorm is a ``custom_vjp``: its hand-written backward must
    come out under ``transpose(jvp(kBatchNorm.<layer>))`` like any
    other layer's."""
    reduces = [
        n for op, n in instructions(conv_net_text)
        if op == "reduce" and f"kBatchNorm.{layer}" in n
    ]
    assert any(f"/jvp(kBatchNorm.{layer})/" in n for n in reduces), reduces
    assert any(
        f"/transpose(jvp(kBatchNorm.{layer}))/" in n for n in reduces
    ), reduces


def test_every_reduction_lies_in_some_layers_scope(conv_net_text):
    for op, name in instructions(conv_net_text):
        if op == "reduce":
            assert re.search(r"k[A-Z]\w+\.\w+", name), name


@pytest.fixture(scope="module")
def engine_texts():
    eng = tiny_engine(spec_k=2)
    slot, chunk = jnp.int32(0), jnp.zeros((4,), jnp.int32)
    draft = jnp.zeros((2, 2), jnp.int32)
    lowered = {
        "_decode": eng._decode_jit.lower(eng.params, eng.state),
        "_prefill": eng._prefill_jit.lower(
            eng.params, eng.state, slot, chunk, jnp.int32(0), jnp.int32(4)
        ),
        "_verify": eng._verify_jit.lower(
            eng.params, eng.state, draft, jnp.zeros((2,), jnp.int32)
        ),
    }
    return {k: v.compile().as_text() for k, v in lowered.items()}


@pytest.mark.parametrize("scope", [
    "blk0/attend/gather_kv", "blk0/attend/kv_write",
    "blk0/attend/cache_attend", "blk1/attend/gather_kv", "blk0/qkv",
    "blk0/mlp", "blk0/ln1", "blk0/attn_out", "embed", "lm_head", "sample",
])
def test_decode_program_names_its_operations(engine_texts, scope):
    names = {n for _, n in instructions(engine_texts["_decode"])}
    assert any(f"jit(_decode)/{scope}/" in n for n in names), scope


@pytest.fixture(scope="module")
def kernel_decode_names():
    """``jit__decode`` of an engine on the paged kernel (interpreted
    here; on a TPU the engine chooses it itself)."""
    eng = tiny_engine(attend_impl="fused")
    text = eng._decode_jit.lower(eng.params, eng.state).compile().as_text()
    return {n for _, n in instructions(text)}


@pytest.mark.parametrize("scope", [
    "blk0/attend/paged_attention", "blk1/attend/paged_attention",
    "blk0/attend/kv_write",
])
def test_decode_under_the_kernel_names_it_inside_attend(
    kernel_decode_names, scope
):
    assert any(
        f"jit(_decode)/{scope}/" in n for n in kernel_decode_names
    ), scope


@pytest.mark.parametrize("scope", ["gather_kv", "cache_attend"])
def test_decode_under_the_kernel_has_no_gather(kernel_decode_names, scope):
    # ``paged_attention_ms_per_tick`` reads the kernel's scope instead;
    # ``kv_gather_ms_per_block_step`` reads this one where it is left
    assert not any(f"/{scope}/" in n for n in kernel_decode_names)


@pytest.mark.parametrize("program", ["_prefill", "_verify"])
def test_prefill_and_verify_share_the_decode_names(engine_texts, program):
    names = {n for _, n in instructions(engine_texts[program])}
    for scope in ("blk0/attend/gather_kv", "blk0/attend/cache_attend",
                  "kv_write", "lm_head"):
        assert any(f"/{scope}/" in n for n in names), (program, scope)
    assert not any("/" in s for s in ("gather_kv", "kv_write", "sample"))


@pytest.mark.parametrize("program", ["_decode", "_prefill", "_verify"])
def test_engine_program_names(engine_texts, program):
    # ``XLA Modules`` events of a trace are keyed by these
    assert f"HloModule jit_{program}," in engine_texts[program]


def test_trainer_chunk_program_name(tmp_path):
    from singa_tpu.trainer import Trainer

    shard = str(tmp_path / "shard")
    write_records(shard, *synthetic_arrays(16, seed=4))
    trainer = Trainer(parse_model_config(f"""
name: "chunk-name"
train_steps: 4
updater {{ base_learning_rate: 0.1 param_type: "Param" }}
neuralnet {{
  layer {{ name: "data" type: "kShardData"
          data_param {{ path: "{shard}" batchsize: 8 }} }}
  layer {{ name: "mnist" type: "kMnistImage" srclayers: "data"
          mnist_param {{ norm_a: 255 norm_b: 0 }} }}
  layer {{ name: "label" type: "kLabel" srclayers: "data" }}
  layer {{ name: "fc" type: "kInnerProduct" srclayers: "mnist"
          inner_product_param {{ num_output: 10 }}
          param {{ name: "w" init_method: "kUniformSqrtFanIn" }}
          param {{ name: "b" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "loss" type: "kSoftmaxLoss" srclayers: "fc" srclayers: "label"
          softmaxloss_param {{ topk: 1 }} }}
}}
"""), seed=0, log=lambda s: None, prefetch=False)
    fn = trainer._make_chunk_fn(2)
    pipes = trainer._pipelines[id(trainer.train_net)]
    text = fn.lower(
        trainer.params, trainer.state, trainer.buffers, jnp.int32(0),
        {name: jnp.int32(0) for name in pipes},
        trainer._dev_data[id(trainer.train_net)],
    ).compile().as_text()
    assert "HloModule jit_chunk_fn," in text
    names = {n for _, n in instructions(text)}
    # inside the scan: the layers' scopes and the update's
    assert any("/update/" in n for n in names)
    assert any("transpose(jvp(kInnerProduct.fc))" in n for n in names)


@pytest.mark.parametrize("kernel", [
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "paged_attention",
])
def test_pallas_kernels_are_named(kernel):
    from singa_tpu.ops.attention import flash_attention
    from singa_tpu.ops.paged_attention import paged_attention

    if kernel == "paged_attention":
        q = jnp.zeros((2, 2, 1, 8))
        pool = jnp.zeros((5, 8, 2 * 8))
        jaxpr = jax.make_jaxpr(lambda q, k, v: paged_attention(
            q, k, v, jnp.zeros((2, 4), jnp.int32),
            jnp.zeros((2, 1), jnp.int32), interpret=True,
        ))(q, pool, pool)
    else:
        x = jnp.zeros((1, 2, 16, 8))

        def loss(q, k, v):
            return flash_attention(
                q, k, v, causal=True, block_q=8, block_k=8, interpret=True
            ).sum()

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    assert f"name={kernel}" in str(jaxpr)


def test_the_grouped_expert_product_is_named(monkeypatch):
    """The grouped form of ``moe_topk_ffn`` (PR 35) runs its three
    products through the kernel that ships with jax (megablox ``gmm``),
    whose ``pallas_call`` takes no ``name=``: what a compiled program's
    text (``%gmm.N = ... custom_call_target="tpu_custom_call"``) and a
    device trace show is its jitted wrapper's name, under the layer's
    own scopes. ``tests/test_chip_compile.py`` reads the same name in
    the text compiled for the chip."""
    from singa_tpu.parallel import moe

    monkeypatch.setattr(moe, "choose_expert_form", lambda *a: "grouped: test")
    eng = tiny_latent_engine()
    slot, chunk = jnp.int32(0), jnp.zeros((4,), jnp.int32)
    jaxpr = str(jax.make_jaxpr(eng._prefill)(
        eng.params, eng.state, slot, chunk, jnp.int32(0), jnp.int32(4)
    ))
    assert "jit[name=gmm " in jaxpr and "pallas_call" in jaxpr
    text = eng._prefill_jit.lower(
        eng.params, eng.state, slot, chunk, jnp.int32(0), jnp.int32(4)
    ).compile().as_text()
    names = {n for _, n in instructions(text)}
    for scope in ("route", "experts", "combine", "shared"):
        assert any("jit(_prefill)/blk1/moe/" in n and f"/{scope}/" in n
                   for n in names), scope
    assert any("/experts/jit(gmm)/" in n for n in names)
    assert any("/combine/jit(gmm)/" in n for n in names)


def test_compile_cache_key_is_salted_by_the_names_version(monkeypatch):
    """JAX leaves names out of the persistent cache's key: an executable
    cached by code that named its operations otherwise would be served
    with its old names, and a trace is read by them. The key holds a
    constant that a renaming change counts up."""
    from jax._src import cache_key

    from singa_tpu.utils import compile_cache

    monkeypatch.setattr(cache_key, "custom_hook", lambda: "")
    monkeypatch.setattr(jax.config, "update", lambda k, v: None)
    compile_cache.setup_compile_cache(log=lambda s: None)
    assert cache_key.custom_hook() == compile_cache.NAMES


# ---------------------------------------------------------------------
# block steps: a model generated by diffusion over blocks
# ---------------------------------------------------------------------


def tiny_block_engine(**serving):
    cfg = TransformerConfig(
        vocab=40, d_model=32, n_heads=4, n_layers=2, max_len=32,
        norm="rmsnorm", pos="rope", n_kv_heads=2, head_dim=8, qk_norm=True,
        tied_head=False, moe_experts=4, moe_top_k=2, moe_d_ff=16,
        diffusion_block=4, mask_id=39,
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)
    return Engine(params, cfg, EngineConfig(
        slots=2, kv_block_len=8, max_prefill_chunk=4, block_steps=2,
        **serving,
    ))


@pytest.fixture(scope="module")
def block_step_text():
    eng = tiny_block_engine()
    return eng._block_step_jit.lower(eng.params, eng.state).compile().as_text()


def test_block_step_program_name(block_step_text):
    # ``jit__block_step`` on the ``XLA Modules`` line of a trace
    assert "HloModule jit__block_step," in block_step_text


@pytest.mark.parametrize("scope", [
    "blk0/qkv", "blk0/qkv/qk_norm", "blk0/qkv/rope",
    "blk0/attend/gather_kv", "blk0/attend/cache_attend", "blk1/kv_write",
    "blk0/moe/route", "blk0/moe/experts", "blk0/moe/combine",
    "blk1/moe/experts", "blk0/ln1", "blk0/attn_out", "embed", "lm_head",
    "sample",
])
def test_block_step_program_names_its_operations(block_step_text, scope):
    names = {n for _, n in instructions(block_step_text)}
    assert any(f"jit(_block_step)/{scope}/" in n for n in names), scope


def test_block_step_under_the_kernel_names_it_inside_attend():
    """On the kernel (interpreted here; on a TPU the engine chooses it
    itself) a block step writes and reads inside ``attend``, which
    ``attend_ms_per_block_step`` reads: the write under ``kv_write``,
    the kernel under ``paged_attention``; no gather, no overlay and no
    write after the forward."""
    eng = tiny_block_engine(attend_impl="fused")
    text = eng._block_step_jit.lower(eng.params, eng.state).compile().as_text()
    names = {n for _, n in instructions(text)}
    for scope in ("blk0/attend/paged_attention", "blk1/attend/paged_attention",
                  "blk0/attend/kv_write", "blk1/attend/kv_write"):
        assert any(f"jit(_block_step)/{scope}/" in n for n in names), scope
    assert not any(
        "/gather_kv/" in n or "/cache_attend/" in n
        or "jit(_block_step)/blk1/kv_write/" in n for n in names
    )


def test_block_step_tick_has_the_unchanged_span_set(tmp_path):
    """A tick that dispatches a block step is read by the same spans as
    a one-token tick: no ``sched.draft``, nothing new."""
    trace_dir = str(tmp_path / "trace")
    sched = Scheduler(tiny_block_engine())
    rs = np.random.RandomState(0)
    with jax.profiler.trace(trace_dir):
        for rid in range(3):
            sched.submit(Request(
                rid=rid, prompt=rs.randint(0, 39, size=(6 + rid,)),
                max_new_tokens=6,
            ))
        sched.serve()
    spans = read_spans(trace_dir)
    assert {s[0] for s in spans if s[0].startswith("sched.")} == (
        set(SCHED_SPANS) - {"sched.draft"}
    )
    for name, _, _, attrs in spans:
        if name in SCHED_SPANS:
            assert set(attrs) >= SCHED_SPANS[name], (name, attrs)
    # one pass ahead: the last pass dispatched is never read
    dispatches = [s for s in spans if s[0] == "sched.dispatch"]
    assert len(dispatches) == sched.decode_ticks + 1
    emitted = sum(s[3]["emitted"] for s in spans if s[0] == "sched.emit")
    assert emitted == sched.tokens_delivered == 18


# ---------------------------------------------------------------------
# a latent cache: latent attention, a dense first layer, held experts
# ---------------------------------------------------------------------


def tiny_latent_engine(**serving):
    cfg = TransformerConfig(
        vocab=40, d_model=32, n_heads=4, n_layers=2, d_ff=48, max_len=32,
        norm="rmsnorm", pos="rope", head_dim=8, tied_head=False,
        mlp="swiglu", dense_layers=1, moe_experts=8, moe_top_k=2,
        moe_d_ff=16, moe_score="sigmoid", moe_bias=True, moe_scale=2.5,
        moe_shared_d_ff=16, moe_held=(2, 4), kv_latent=16, q_latent=24,
        rope_dim=4, v_head_dim=8, rope_yarn=(4.0, 16, 1, 1, 1.0, 1.0),
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)
    return Engine(params, cfg, EngineConfig(
        slots=2, kv_block_len=8, max_prefill_chunk=4, **serving
    ))


def test_latent_decode_under_the_kernel_names_it_inside_attend():
    """The latent kernel (interpreted here; on a TPU the engine chooses
    it itself) runs under the scope ``paged_attention_ms_per_tick``
    reads, with the query's way into the latent space and back beside
    it, and no gather."""
    eng = tiny_latent_engine(attend_impl="fused")
    text = eng._decode_jit.lower(eng.params, eng.state).compile().as_text()
    names = {n for _, n in instructions(text)}
    for scope in ("blk0/attend/paged_attention", "blk1/attend/paged_attention",
                  "blk0/attend/paged_attention/absorb",
                  "blk0/attend/paged_attention/lift", "blk0/attend/kv_write"):
        assert any(f"jit(_decode)/{scope}/" in n for n in names), scope
    assert not any("/gather_kv/" in n or "/cache_attend/" in n for n in names)
    jaxpr = str(jax.make_jaxpr(eng._decode)(eng.params, eng.state))
    assert "name=paged_latent_attention" in jaxpr


@pytest.fixture(scope="module")
def latent_texts():
    eng = tiny_latent_engine()
    slot, chunk = jnp.int32(0), jnp.zeros((4,), jnp.int32)
    lowered = {
        "_decode": eng._decode_jit.lower(eng.params, eng.state),
        "_prefill": eng._prefill_jit.lower(
            eng.params, eng.state, slot, chunk, jnp.int32(0), jnp.int32(4)
        ),
    }
    return {k: v.compile().as_text() for k, v in lowered.items()}


@pytest.mark.parametrize("program,scope", [
    # the names the benchmark's readers know, with the finer ones inside
    ("_decode", "blk0/qkv/q_latent"), ("_decode", "blk0/qkv/kv_latent"),
    ("_decode", "blk1/qkv/kv_latent/rope"),
    ("_decode", "blk0/attend/kv_write"), ("_decode", "blk1/attend/gather_kv"),
    ("_decode", "blk0/attend/cache_attend"),
    ("_decode", "blk1/attend/cache_attend/absorb"),
    ("_decode", "blk1/attend/cache_attend/lift"),
    ("_decode", "blk0/mlp"), ("_decode", "blk1/moe/route"),
    ("_decode", "blk1/moe/experts"), ("_decode", "blk1/moe/combine"),
    ("_decode", "blk1/moe/shared"), ("_decode", "blk0/attn_out"),
    ("_decode", "lm_head"), ("_decode", "sample"),
    ("_prefill", "blk0/attend/cache_attend/while/body/materialise"),
    ("_prefill", "blk1/attend/gather_kv"), ("_prefill", "blk1/moe/shared"),
    ("_prefill", "blk0/attend/kv_write"), ("_prefill", "blk0/mlp"),
])
def test_latent_programs_name_their_operations(latent_texts, program, scope):
    assert f"HloModule jit_{program}," in latent_texts[program]
    names = {n for _, n in instructions(latent_texts[program])}
    assert any(f"jit({program})/{scope}/" in n for n in names), scope


def test_latent_scope_names_are_plain_segments(latent_texts):
    """No new name holds the separator of a scope path, and what the
    benchmark's readers book device time to is a name they know: the
    finer scopes lie INSIDE ``qkv``, ``cache_attend`` and ``moe``."""
    from benchmark import program_trace

    new = ("q_latent", "kv_latent", "absorb", "lift", "materialise", "shared")
    assert not any("/" in n for n in new)
    assert not any(program_trace.KNOWN.match(n) for n in new)
    for text in latent_texts.values():
        for _, name in instructions(text):
            parts = name.split("/")
            for n in new:
                if n in parts:
                    assert any(
                        program_trace.KNOWN.match(p) for p in parts[:parts.index(n)]
                        if not p.startswith("blk")
                    ), name
    # the absorbed tick forms no key or value of a head: the decode
    # program holds no ``materialise``, the chunk no ``absorb``
    assert "materialise" not in latent_texts["_decode"]
    assert "absorb" not in latent_texts["_prefill"]


# ---------------------------------------------------------------------
# one-mixer layers: Mamba-2 with recurrent state, latent ReLU^2 experts
# ---------------------------------------------------------------------


def tiny_hybrid_engine(**serving):
    cfg = TransformerConfig(
        vocab=40, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        n_layers=3, layers=("attn", "moe", "mamba"), d_ff=24, max_len=32,
        norm="rmsnorm", pos="none", mlp="relu2", tied_head=False,
        mamba_heads=8, mamba_head_dim=4, ssm_state=8, ssm_groups=2,
        ssm_block=4, moe_experts=8, moe_top_k=3, moe_d_ff=16,
        moe_score="sigmoid", moe_bias=True, moe_scale=5.0,
        moe_shared_d_ff=24, moe_held=(2, 4), moe_act="relu2", moe_latent=16,
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)
    return Engine(params, cfg, EngineConfig(
        slots=2, kv_block_len=8, max_prefill_chunk=8, **serving
    ))


@pytest.fixture(scope="module")
def hybrid_texts():
    eng = tiny_hybrid_engine()
    slot, chunk = jnp.int32(0), jnp.zeros((8,), jnp.int32)
    lowered = {
        "_decode": eng._decode_jit.lower(eng.params, eng.state),
        "_prefill": eng._prefill_jit.lower(
            eng.params, eng.state, slot, chunk, jnp.int32(0), jnp.int32(8)
        ),
    }
    return {k: v.compile().as_text() for k, v in lowered.items()}


@pytest.mark.parametrize("program,scope", [
    # the Mamba layer's own scopes: one ``step`` a tick, the chunked
    # ``scan`` (its carry a loop) for a chunk
    ("_decode", "blk2/mamba/in_proj"), ("_decode", "blk2/mamba/conv"),
    ("_decode", "blk2/mamba/step"), ("_decode", "blk2/mamba/gate_norm"),
    ("_decode", "blk2/mamba/out_proj"),
    ("_prefill", "blk2/mamba/in_proj"), ("_prefill", "blk2/mamba/conv"),
    ("_prefill", "blk2/mamba/scan"), ("_prefill", "blk2/mamba/gate_norm"),
    ("_prefill", "blk2/mamba/out_proj"),
    # the latent projections beside the expert layer's known names
    ("_decode", "blk1/moe/latent_down"), ("_decode", "blk1/moe/latent_up"),
    ("_decode", "blk1/moe/route"), ("_decode", "blk1/moe/experts"),
    ("_decode", "blk1/moe/combine"), ("_decode", "blk1/moe/shared"),
    ("_prefill", "blk1/moe/latent_down"), ("_prefill", "blk1/moe/latent_up"),
    # attention in its one layer, by the names the readers know
    ("_decode", "blk0/attend/kv_write"), ("_decode", "blk0/attend/gather_kv"),
    ("_decode", "blk0/attend/cache_attend"), ("_decode", "blk0/attn_out"),
    ("_decode", "blk0/ln1"), ("_decode", "blk2/ln1"),
    ("_decode", "lm_head"), ("_decode", "sample"), ("_decode", "embed"),
])
def test_hybrid_programs_name_their_operations(hybrid_texts, program, scope):
    assert f"HloModule jit_{program}," in hybrid_texts[program]
    names = {n for _, n in instructions(hybrid_texts[program])}
    assert any(f"jit({program})/{scope}/" in n for n in names), scope


def test_hybrid_scope_names_and_counters():
    """A one-mixer block has no second norm and no mixer it was not
    given; the tick holds no ``scan`` and the chunk no ``step``; the new
    names are plain segments; and the pass's counters end in the state's
    own (``STATE_COUNTERS``), after ``DECODE_COUNTERS`` unchanged."""
    from singa_tpu.ops import ssm
    from singa_tpu.serve import engine as engine_mod

    eng = tiny_hybrid_engine()
    assert eng.decode_counter_names == (
        "experts_hit", "expert_max_load", "held_pairs", "cache_rows",
        "chunk_held_pairs", "state_slots_live",
    ) == engine_mod.DECODE_COUNTERS + engine_mod.STATE_COUNTERS
    assert set(eng.mamba_forms) == {"jit__decode", "jit__prefill"}
    assert not any("/" in n for n in ssm.MAMBA_PARAMS + (
        "mamba", "in_proj", "conv", "scan", "step", "gate_norm", "out_proj",
        "latent_down", "latent_up",
    ))
    jaxpr = str(jax.make_jaxpr(eng._decode)(eng.params, eng.state))
    assert "pallas_call" not in jaxpr      # no kernel here: plain XLA


def test_hybrid_blocks_hold_their_one_mixer(hybrid_texts):
    for program, text in hybrid_texts.items():
        names = {n for _, n in instructions(text)}
        assert not any("/ln2/" in n for n in names)
        assert not any("/blk0/moe/" in n or "/blk0/mamba/" in n for n in names)
        assert not any("/blk2/attend/" in n or "/blk1/attend/" in n
                       for n in names)
    decode = {n for _, n in instructions(hybrid_texts["_decode"])}
    prefill = {n for _, n in instructions(hybrid_texts["_prefill"])}
    assert not any("/mamba/scan/" in n for n in decode)
    assert not any("/mamba/step/" in n for n in prefill)


# ---------------------------------------------------------------------
# short convolutions: a tail alone, beside rotated GQA attention
# ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def rag_texts():
    cfg = TransformerConfig(
        vocab=40, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        n_layers=3, layers=("shortconv", "attn", "moe"), d_ff=24,
        max_len=32, norm="rmsnorm", pos="rope", qk_norm=True, mlp="swiglu",
        conv_kernel=3, moe_experts=4, moe_top_k=2, moe_d_ff=16,
        moe_score="sigmoid", moe_bias=True,
    )
    eng = Engine(init_lm(jax.random.PRNGKey(0), cfg), cfg, EngineConfig(
        slots=2, kv_block_len=8, max_prefill_chunk=8,
    ))
    slot, chunk = jnp.int32(0), jnp.zeros((8,), jnp.int32)
    lowered = {
        "_decode": eng._decode_jit.lower(eng.params, eng.state),
        "_prefill": eng._prefill_jit.lower(
            eng.params, eng.state, slot, chunk, jnp.int32(0), jnp.int32(8)
        ),
    }
    return eng, {k: v.compile().as_text() for k, v in lowered.items()}


@pytest.mark.parametrize("program,scope", [
    # the short convolution's own scopes, read by shortconv_ms_per_tick
    # and shortconv_ms_per_chunk
    ("_decode", "blk0/shortconv/in_proj"), ("_decode", "blk0/shortconv/conv"),
    ("_decode", "blk0/shortconv/out_proj"),
    ("_prefill", "blk0/shortconv/in_proj"),
    ("_prefill", "blk0/shortconv/conv"), ("_prefill", "blk0/shortconv/out_proj"),
    # rotated, QK-normed attention in the one-mixer path, by the names
    # the readers know
    ("_decode", "blk1/qkv/qk_norm"), ("_decode", "blk1/qkv/rope"),
    ("_decode", "blk1/attend/kv_write"), ("_decode", "blk1/attn_out"),
    ("_prefill", "blk1/qkv/rope"), ("_prefill", "blk1/attend/cache_attend"),
    ("_decode", "blk2/moe/route"), ("_decode", "blk0/ln1"),
    ("_decode", "lm_head"), ("_decode", "sample"),
])
def test_rag_programs_name_their_operations(rag_texts, program, scope):
    _, texts = rag_texts
    assert f"HloModule jit_{program}," in texts[program]
    names = {n for _, n in instructions(texts[program])}
    assert any(f"jit({program})/{scope}/" in n for n in names), scope


def test_rag_scope_names_and_counters(rag_texts):
    """``shortconv`` is a plain segment that no reader of
    ``program_trace.KNOWN`` books time to (its readers find it by name);
    the block holds no other mixer; and a model whose only recurrent
    state is a tail counts its live slots as Mamba's does."""
    from benchmark import program_trace
    from singa_tpu.ops import ssm
    from singa_tpu.serve import engine as engine_mod

    eng, texts = rag_texts
    assert not any("/" in n for n in ssm.SHORTCONV_PARAMS + ("shortconv",))
    assert not program_trace.KNOWN.match("shortconv")
    assert eng.decode_counter_names == (
        engine_mod.DECODE_COUNTERS + engine_mod.STATE_COUNTERS
    )
    for text in texts.values():
        names = {n for _, n in instructions(text)}
        assert not any("/blk0/attend/" in n or "/mamba/" in n for n in names)
        assert not any("/blk1/shortconv/" in n for n in names)
