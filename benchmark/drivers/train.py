"""Driver ``train``: a conf-trained net through the program's Trainer.

Set-up writes seed-made records with the program's own record writer,
renders the job file from the configuration's layer list, builds ONE
``Trainer`` from it (``load_model_config``), installs seed-made weights,
and drives that trainer through its first steps: step 0 alone (a chunk
of one step, a compiled program of its own, run only so that the first
gradient can be read from the optimizer's state), then ONE chunk of the
window's length through the window's own ``run_chunk`` — the compiled
program, the call and the read-out that the window drives. That chunk's
mean loss and the parameters' change across it are what the output
check reads, and it is the warm-up too. The window hands the SAME
trainer chunk after chunk of ``chunk_steps`` steps, waiting for each as
``Trainer.run()`` does at a display, until the seconds are up.

After the window the trainer is dropped and the plain reference
(``benchmark/reference/confnet.py``) follows those ``1 + chunk_steps``
steps from the same weights and rows.
"""

from __future__ import annotations

import gc
import importlib
import math
import os
import statistics
import time

import numpy as np

from benchmark import weights
from benchmark.models import confnet

def make_records(config: dict, traffic: dict, seed: int):
    """Seed-made records as uint8 arrays: (images or tokens, labels)."""
    rng = np.random.default_rng(seed)
    n = traffic["records"]
    if config["kind"] == "image":
        edge = config["crop"]
        images = rng.integers(0, 256, size=(n, 3, edge, edge), dtype=np.uint8)
        labels = rng.integers(0, config["classes"], size=(n,)).astype(np.int32)
        return images, labels
    # token records are one byte a token in the program's record format
    top = min(config["vocab_size"], 256)
    tokens = rng.integers(0, top, size=(n, traffic["seq_len"]), dtype=np.uint8)
    return tokens, np.zeros((n,), np.int32)


def leaf_norms(tree: dict, base: dict | None = None) -> dict[str, float]:
    """Per-leaf L2 norms of ``tree`` (or of ``tree - base``), pulled to
    the host as plain floats."""
    import jax
    import jax.numpy as jnp

    def norms(tree, base):
        return {
            k: jnp.sqrt(jnp.sum(jnp.square(
                (v if base is None else v - base[k]).astype(jnp.float32)
            )))
            for k, v in tree.items()
        }

    return {k: float(v) for k, v in jax.jit(norms)(tree, base).items()}


def leaf_gaps(got: dict, want: dict, skip=()) -> dict[str, float]:
    """The gap between the program's norm and the reference's, leaf by
    leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    floor = statistics.median(want.values())
    out = {}
    for k, w in want.items():
        if k not in skip:
            gap = abs(got[k] - w) / max(w, floor, 1e-30)
            out[k] = gap if math.isfinite(gap) else float("inf")
    return out


def compare(program: dict, reference: dict, matrices=()) -> dict:
    """The numbers of a training cell from the two sides' readings
    ({"losses": [step 0's, the chunk's mean], "history1", "change"}):
    the losses, the first gradient as the optimizer got it (its state
    after step 0) and the parameters' change across the window's first
    chunk, each by the worst leaf, by the worst of ``matrices`` (the
    leaves of two or more dimensions: the operands of the products) and
    by the median leaf. ``limits/<workload>.json`` says which of them a
    cell compares: the median and the matrices are the steady ones where
    the worst leaf is a small vector whose sum cancels."""
    loss_gap = max(
        abs(p - r) / max(abs(r), 1e-30)
        for p, r in zip(program["losses"], reference["losses"])
    )
    if not all(math.isfinite(p) for p in program["losses"]):
        loss_gap = float("inf")
    grad = leaf_gaps(program["history1"], reference["history1"])
    # leaves whose first gradient is nought to rounding in the reference
    # move by round-off alone: left out of the change by a rule on the
    # reference's gradient, not by name
    floor = 1e-3 * statistics.median(reference["history1"].values())
    skip = {k for k, v in reference["history1"].items() if v < floor}
    change = leaf_gaps(program["change"], reference["change"], skip)
    grad_at, change_at = max(grad, key=grad.get), max(change, key=change.get)
    out = {
        "loss_gap": loss_gap,
        "grad_gap": grad[grad_at], "change_gap": change[change_at],
        "grad_gap_median": statistics.median(grad.values()),
        "change_gap_median": statistics.median(change.values()),
        "grad_at": grad_at, "change_at": change_at, "skipped": len(skip),
    }
    for name, gaps in (("grad", grad), ("change", change)):
        inside = {k: v for k, v in gaps.items() if k in matrices}
        if inside:
            at = max(inside, key=inside.get)
            out[f"{name}_gap_matrices"] = inside[at]
            out[f"{name}_matrices_at"] = at
    return out


def judged(numbers: dict, limits: dict) -> dict:
    """The numbers a cell compares, each beside its limit."""
    return {
        k: {"value": numbers.get(k), "limit": limits[k]} for k in limits
    }


class Driver:
    def __init__(self, *, config, traffic, limits, seed, devices, work, spans):
        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed, self.devices, self.work, self.spans = (
            seed, devices, work, spans,
        )
        self.trainer = None
        self.steps_done = self.steps_failed = 0
        self.window_s = 0.0
        self.last_loss = float("nan")
        self.program: dict = {}

    # -- set-up ---------------------------------------------------------

    def build(self):
        """Records, job file, Trainer, weights. -> the trainer."""
        import jax

        from singa_tpu.config import load_model_config
        from singa_tpu.data.loader import write_records
        from singa_tpu.trainer import Trainer

        if "generator" not in self.config:
            raise RuntimeError(
                f"driver train: configuration {self.config.get('name')!r} "
                f"names no \"generator\" (benchmark/models/<generator>.py, "
                f"which builds its layer list); a configuration that is "
                f"only served needs none"
            )
        shard = os.path.join(self.work, "shard")
        self.records = make_records(self.config, self.traffic, self.seed)
        write_records(shard, *self.records)
        gen = importlib.import_module(
            f"benchmark.models.{self.config['generator']}"
        )
        self.layers = gen.build(self.config, self.traffic, shard)
        conf = os.path.join(self.work, "job.conf")
        with open(conf, "w") as f:
            f.write(confnet.render(
                self.config["name"], self.layers, self.config["updater"],
                self.config["compute_dtype"],
                tail="train_steps: 1000000000\ntest_steps: 0\n"
                "test_frequency: 0\ndisplay_frequency: 0\n"
                "checkpoint_frequency: 0",
            ))
        trainer = Trainer(
            load_model_config(conf), seed=0, log=lambda s: None,
            prefetch=False,
        )
        if trainer.feeder_mode != "cached":
            raise RuntimeError(
                f"records not cached on the device: {trainer.feeder_mode}"
            )
        self.specs = confnet.param_specs(self.layers)
        w0 = weights.make(self.specs, self.seed)
        have = {k: tuple(v.shape) for k, v in trainer.params.items()}
        want = {k: tuple(v.shape) for k, v in w0.items()}
        if have != want:
            odd = sorted(set(have.items()) ^ set(want.items()))[:6]
            raise RuntimeError(f"layer list and program disagree: {odd}")
        trainer.params = {
            k: jax.device_put(v, trainer.param_sh[k]) for k, v in w0.items()
        }
        return trainer

    def first_steps(self, trainer) -> dict:
        """Step 0 alone, then the window's first chunk through
        ``run_chunk``. -> the program's readings. ``trainer`` becomes
        the driver's own: the one the window drives."""
        import jax
        import jax.numpy as jnp

        self.trainer, self.step = trainer, 0
        trainer.perf.reset()
        with self.spans.span("first_step"):
            trainer.train_chunk(0, 1)
        (m,) = trainer.perf.avg().values()
        trainer.perf.reset()
        loss0, self.step = float(m["loss"]), 1
        history1 = leaf_norms(
            {k: v["history"] for k, v in trainer.state.items()}
        )
        # a copy: the trainer's steps donate the arrays they are given
        w1 = jax.tree.map(jnp.copy, trainer.params)
        chunk_loss = self.run_chunk(self.traffic["chunk_steps"])
        change = leaf_norms(trainer.params, w1)
        return {
            "losses": [loss0, chunk_loss], "history1": history1,
            "change": change,
        }

    def setup(self) -> None:
        import jax

        # the checked chunk is the window's own program and warms it
        self.program = self.first_steps(self.build())
        jax.block_until_ready(self.trainer.params)

    # -- the window -----------------------------------------------------

    def run_chunk(self, k: int) -> float:
        """One chunk as ``Trainer.run()`` runs it with a display at its
        end: dispatch, then pull the chunk's mean metrics in one
        transfer (the display's host sync). -> the chunk's mean loss."""
        trainer = self.trainer
        with self.spans.span("train_chunk", steps=k):
            trainer.train_chunk(self.step, k)
        with self.spans.span("chunk_wait", steps=k):
            (m,) = trainer.perf.avg().values()
        trainer.perf.reset()
        self.step += k
        return float(m["loss"])

    def window(self, seconds: float) -> None:
        import jax

        k = self.traffic["chunk_steps"]
        t0 = time.perf_counter()
        while True:
            loss = self.run_chunk(k)
            self.steps_done += k
            self.last_loss = loss
            if not math.isfinite(loss):
                self.steps_failed += k
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(self.trainer.params)
        self.window_s += time.perf_counter() - t0

    def end_to_end(self) -> dict:
        return {"train_step_ms": 1000.0 * self.window_s / self.steps_done}

    def counters(self) -> dict:
        return {
            "steps": self.steps_done, "window_s": self.window_s,
            "batch": self.traffic["batch"], "last_chunk_loss": self.last_loss,
        }

    def attempted_failed(self) -> tuple[int, int]:
        return self.steps_done, self.steps_failed

    def step_flops(self) -> float:
        from benchmark import flops

        return flops.train_step_flops(self.layers, self.traffic)

    # -- after the window -----------------------------------------------

    def release(self) -> None:
        import jax

        self.trainer = None
        gc.collect()
        jax.clear_caches()

    def reference_readings(self, arith: str = "float32",
                           fault: str | None = None) -> dict:
        """What the plain reference reads over the same steps: step 0,
        then the ``chunk_steps`` of the first chunk. With ``arith``
        below float32 it is the control; ``fault`` plants one of the
        faults a training cell can have in it (``half_batch``: half of
        each batch left out, the mean taken over the rest)."""
        import jax.numpy as jnp

        from benchmark.reference import confnet as ref

        b, k = self.traffic["batch"], self.traffic["chunk_steps"]
        rows = b // 2 if fault == "half_batch" else b
        images, labels = self.records
        n = len(labels) // b  # the trainer reads the rows round and round
        batches = [
            {"image": jnp.asarray(images[(s % n) * b:(s % n) * b + rows]),
             "label": jnp.asarray(labels[(s % n) * b:(s % n) * b + rows])}
            for s in range(1 + k)
        ]
        w0 = weights.make(self.specs, self.seed)
        losses, history1, w1, params = ref.train_steps(
            self.layers, self.config["updater"], w0, batches, arith
        )
        losses = [float(x) for x in losses]
        return {
            "losses": [losses[0], sum(losses[1:]) / k],
            "history1": leaf_norms(history1),
            "change": leaf_norms(params, w1),
        }

    def matrices(self) -> set:
        return {k for k, v in self.specs.items() if len(v["shape"]) >= 2}

    def calibrate(self, controls=(), faults=(), seconds=None) -> dict:
        """One seed's readings for setting limits: the program against
        the reference, and each control and planted fault (the
        reference put in the program's place) against it. Training's
        readings need no measured window, so ``seconds`` is unused."""
        program = self.first_steps(self.build())
        self.release()
        reference = self.reference_readings()
        sides = {"program": program}
        for arith in controls:
            sides[arith] = self.reference_readings(arith)
        for fault in faults:
            sides[fault] = self.reference_readings(fault=fault)
        return {
            name: compare(side, reference, self.matrices())
            for name, side in sides.items()
        }

    def check(self) -> dict:
        return judged(
            compare(self.program, self.reference_readings(), self.matrices()),
            self.limits,
        )
