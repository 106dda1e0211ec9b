"""A cell is added by files and entries alone, and the rehearsal follows
it by name.

Into a temporary copy of ``BENCHMARK.json``, of the benchmark's data
directories and of the rehearsal's tiny directories this adds what a
``model_config`` PR adds (``benchmark/README.md``, "A new cell /
configuration / driver / metric"), and edits no file that is there:

    benchmark/configs/toy_lm.json            a configuration, keys of its own
    benchmark/traffic/closed_toy.json        a traffic mix, naming its driver
    benchmark/drivers/toy_lm.py              the driver: a subclass of serve's
    benchmark/limits/toy_lm_serve_closed.json
    benchmark/metrics/toy_ticks_per_request.py   a per-layer reader
    tests/benchmark/tiny/configs/toy_lm.json
    tests/benchmark/tiny/traffic/closed_toy.json
    tests/benchmark/tiny/limits/toy_lm_serve_closed.json   (a cell MAY)
    BENCHMARK.json    one ``configs[]``, one ``workloads[]`` and one
                      ``per_layer[]`` entry; the cell's name appended to the
                      ``workloads`` of the metrics its driver feeds

(The driver "file" is the class below, registered as the module
``benchmark.drivers.toy_lm``.) ``run.main`` then passes for the new cell
in both ``--trace`` modes and for every shipped cell beside it; with the
new cell's tiny traffic file left out the new cell fails, naming the
file, and the shipped cells do not notice.

The driver is the second satellite's proof too: a second served LM is a
subclass that overrides ``make_engine``, ``token_fwd_flops`` and the
reference, and the closed loop is not copied.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.drivers import serve  # noqa: E402

CELL = "toy_lm_serve_closed"

#: the "published" configuration: other key names than GPT-2's, and a
#: size that no test runs (the tiny file is laid over it)
CONFIG = {
    "name": "toy_lm", "kind": "tokens",
    "hidden_size": 256, "num_hidden_layers": 4, "num_attention_heads": 4,
    "intermediate_size": 512, "vocab_size": 1000,
    "max_position_embeddings": 256, "norm_eps": 1e-5, "init_std": 0.02,
}
TINY_CONFIG = {
    "hidden_size": 16, "num_hidden_layers": 2, "num_attention_heads": 2,
    "intermediate_size": 48, "vocab_size": 200,
    "max_position_embeddings": 48, "init_std": 0.3,
}
TRAFFIC = {
    "driver": "toy_lm", "callers": 16, "slots": 16, "kv_block_len": 16,
    "kv_blocks": 0, "max_prefill_chunk": 64,
    "prompt_len": {"median": 64, "sigma": 0.5, "min": 16, "max": 128},
    "output_len": {"median": 32, "sigma": 0.5, "min": 8, "max": 64},
    "pool": 32, "shape_seed": 3, "greedy": True, "check_requests": 4,
}
TINY_TRAFFIC = {
    "driver": "toy_lm", "callers": 3, "slots": 3, "kv_block_len": 8,
    "kv_blocks": 0, "max_prefill_chunk": 8,
    "prompt_len": {"median": 10, "sigma": 0.5, "min": 4, "max": 24},
    "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
    "pool": 12, "shape_seed": 3, "greedy": True, "check_requests": 3,
    "trace_seconds": 0.3,
}
READER = '''"""Scheduler ticks a finished request: a count from the driver's
counters. Moves serve_tokens_per_s."""


def read(run):
    c = run["counters"]
    if not c.get("requests_finished"):
        return None
    return c["ticks"] / c["requests_finished"]
'''
#: the shipped metrics that a subclass of the closed loop feeds whatever
#: it serves (the loop's own spans and counters); the readers of
#: ``program_trace`` want names inside the program and a recorded cut
FED = ("serve_tokens_per_s", "serve_itl_p95_ms", "step_mfu.serve",
       "decode_tick_ms", "serve_ttft_p95_ms")


class ToyDriver(serve.Driver):
    """A served LM under other key names: the three methods, no loop."""

    def _gpt2_keys(self) -> dict:
        c = self.config
        return {
            "n_embd": c["hidden_size"], "n_layer": c["num_hidden_layers"],
            "n_head": c["num_attention_heads"],
            "n_inner": c["intermediate_size"], "vocab_size": c["vocab_size"],
            "n_positions": c["max_position_embeddings"],
            "layer_norm_epsilon": c["norm_eps"],
            "initializer_range": c["init_std"],
        }

    def make_engine(self) -> None:
        from singa_tpu.models.transformer import TransformerConfig
        from singa_tpu.serve import Engine, EngineConfig, Scheduler

        from benchmark import weights

        c, t = self.config, self.traffic
        self.mcfg = TransformerConfig(
            vocab=c["vocab_size"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
            d_ff=c["intermediate_size"], max_len=c["max_position_embeddings"],
        )
        self.engine = Engine(
            weights.make(self.reference_specs(), self.seed), self.mcfg,
            EngineConfig(
                slots=t["slots"], kv_block_len=t["kv_block_len"],
                kv_blocks=t["kv_blocks"],
                max_prefill_chunk=t["max_prefill_chunk"],
            ),
        )
        self.sched = Scheduler(self.engine)

    flops_calls = 0

    def token_fwd_flops(self, position: int) -> float:
        self.flops_calls += 1
        return 1000.0 + position

    def reference_specs(self) -> dict:
        from benchmark.reference import lm

        return lm.lm_specs(self._gpt2_keys())

    def reference_forward(self, params, seq, arith: str = "float32"):
        from benchmark.reference import lm

        return lm.forward(params, seq, self._gpt2_keys(), arith)


@pytest.fixture()
def toy_driver_registered(monkeypatch):
    """``benchmark/drivers/toy_lm.py``, as the harness imports it."""
    mod = types.ModuleType("benchmark.drivers.toy_lm")
    mod.Driver = ToyDriver
    monkeypatch.setitem(sys.modules, "benchmark.drivers.toy_lm", mod)


def shipped_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["workloads"]]


def add_the_cell(tree: str, tiny_traffic: bool = True) -> None:
    """The files and entries of the module's docstring, into ``tree``."""

    def write(rel: str, content) -> None:
        path = os.path.join(tree, rel)
        assert not os.path.exists(path), f"{rel} is there: that is an edit"
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(content if isinstance(content, str) else json.dumps(content))

    write("benchmark/configs/toy_lm.json", CONFIG)
    write("benchmark/traffic/closed_toy.json", TRAFFIC)
    write(f"benchmark/limits/{CELL}.json", {"logit_gap": 0.05})
    write(f"tests/benchmark/tiny/limits/{CELL}.json", {"logit_gap": 2e-4})
    write("benchmark/metrics/toy_ticks_per_request.py", READER)
    write("tests/benchmark/tiny/configs/toy_lm.json", TINY_CONFIG)
    if tiny_traffic:
        write("tests/benchmark/tiny/traffic/closed_toy.json", TINY_TRAFFIC)

    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy_lm", "source": "tests/benchmark/test_new_cell.py",
        "file": "benchmark/configs/toy_lm.json", "reduced": [],
        "why": "a second served LM, to show that one is added by files",
    })
    bench["workloads"].append({
        "name": CELL, "config": "toy_lm", "traffic": "closed_toy", "chips": 1,
        "why": "closed loop under a new traffic name and a driver of its own",
    })
    bench["per_layer"].append({
        "name": "toy_ticks_per_request", "unit": "ticks", "better": "lower",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_tokens_per_s", "workloads": [CELL],
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in FED:
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)


def copy_of_the_tree(tmp: str) -> str:
    """BENCHMARK.json, the benchmark's data directories and the
    rehearsal's, copied: what a PR that adds a cell adds files to."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    for rel in ("benchmark/configs", "benchmark/traffic", "benchmark/limits",
                "benchmark/metrics", "tests/benchmark/tiny",
                "tests/benchmark/data"):
        shutil.copytree(
            os.path.join(ROOT, rel), os.path.join(tmp, rel),
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    return tmp


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    out = copy_of_the_tree(str(tmp_path_factory.mktemp("tree")))
    add_the_cell(out)
    return out


@pytest.fixture(scope="module")
def tree_without_tiny_traffic(tmp_path_factory):
    out = copy_of_the_tree(str(tmp_path_factory.mktemp("tree_short")))
    add_the_cell(out, tiny_traffic=False)
    return out


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [CELL, *shipped_cells()])
def test_new_cell_and_the_shipped_cells_beside_it(
    rehearse, toy_driver_registered, tree, cell, trace
):
    last = rehearse(cell, trace, tree)
    if cell == CELL:
        want = {"toy_ticks_per_request", "step_mfu.serve", "decode_tick_ms",
                "serve_ttft_p95_ms"} if trace else {
            "serve_tokens_per_s", "serve_itl_p95_ms", "setup_s"}
        assert set(last["metrics"]) == want
    else:
        assert "toy_ticks_per_request" not in last["metrics"]


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_without_its_tiny_traffic_fails_naming_the_file(
    rehearse, toy_driver_registered, tree_without_tiny_traffic, trace
):
    with pytest.raises(pytest.fail.Exception) as e:
        rehearse(CELL, trace, tree_without_tiny_traffic)
    assert "tests/benchmark/tiny/traffic/closed_toy.json" in str(e.value)


@pytest.mark.parametrize("cell", shipped_cells())
def test_shipped_cells_do_not_notice_a_new_cell_that_is_short_of_a_file(
    rehearse, tree_without_tiny_traffic, cell
):
    rehearse(cell, 0, tree_without_tiny_traffic)


def test_a_cell_without_a_recorded_cut_is_told_how_to_record_one(
    recorded_program_trace, tmp_path
):
    """A cell whose per-layer metric reads ``program_trace`` finds its
    cut by its own name; without one the message gives the command."""
    from benchmark import program_trace

    recorded_program_trace(str(tmp_path))  # a directory with no cuts
    trace_dir = tmp_path / ".bench_work" / CELL / "trace"
    path = trace_dir / "plugins" / "profile" / "t" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    with pytest.raises(pytest.fail.Exception) as e:
        program_trace.load(str(trace_dir))
    assert "benchmark/program_trace.py <trace dir> --json" in str(e.value)
    assert f"scopes_{CELL}.json" in str(e.value)


def test_serve_loop_through_a_subclass_with_a_model_of_its_own(
    tiny_files, tree, tmp_path
):
    """The closed loop, its stamps, the sample and the output check,
    driven through a subclass that overrides the three methods: nothing
    else of ``drivers/serve.py`` knows the model."""
    import jax

    from benchmark import run as harness

    files = tiny_files(tree)
    d = ToyDriver(
        config=files.config("toy_lm"), traffic=files.traffic("closed_toy"),
        limits=files.limits(CELL), seed=2**31 + 5,
        devices=jax.devices()[:1], work=str(tmp_path),
        spans=harness.Spans(True),  # a traced run: engine spans and FLOPs
    )
    assert d.limits == {"logit_gap": 2e-4}  # the cell's own tiny limits
    d.setup()
    assert d.mcfg.d_model == 16 and d.mcfg.n_layers == 2
    d.window(0.5)
    counters = d.counters()
    attempted, failed = d.attempted_failed()
    assert attempted > 0 and failed == 0
    # the subclass's own count of FLOPs, one call a token processed
    assert d.flops_calls > 0
    tokens = d.flops_calls
    assert 1000.0 * tokens < counters["model_flops"] <= (1000.0 + 48) * tokens
    assert d.spans.named("decode") and d.spans.named("prefill_chunk")
    d.release()
    assert d.engine is None and d.sample
    compared = d.check()
    assert harness.passes(compared), compared
    # the control through the subclass's reference: a lower precision fails
    assert d.logit_gaps(d.sample, "float8") > d.limits["logit_gap"]


def test_train_driver_says_when_a_configuration_names_no_generator(
    tiny_files, tree, tmp_path
):
    """A configuration that is only served names no ``generator``; put
    under a training traffic mix, the driver says what is missing."""
    from benchmark import run as harness
    from benchmark.drivers import train

    files = tiny_files(tree)
    d = train.Driver(
        config=files.config("toy_lm"), traffic={"driver": "train"},
        limits={}, seed=1, devices=[], work=str(tmp_path),
        spans=harness.Spans(False),
    )
    with pytest.raises(RuntimeError, match="names no \"generator\""):
        d.build()
