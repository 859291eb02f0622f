"""Runner for training cells (traffic kind ``train``): the window drives
``ComputationGraph.fit_batch()`` on staged, seeded token batches. General
over model families: the net with the seed's weights, the ids the batches
draw (``sizes["vocab"]``), the leaf-by-leaf view of the program's state and
the plain reference come from ``ctx.family`` (``families/README.md``).

Set-up builds ONE net with its compiled step and state, drives it from the
seed through its first steps by the window's own call and feed, reads what
the comparison needs (each step's loss; after step one the first gradient's
per-leaf norm, worked out from Adam's first moment; after step three the
per-leaf norm of the parameters' change), and hands that same net to the
window. The window counts whole steps and ends in a host readback of the
loss. After it, the net is freed and the plain reference follows the same
first three steps. ``calibrate`` is ``benchmark/calibrate.py``'s readings
for this kind of traffic: many seeds from one compiled step.
"""

from __future__ import annotations

import collections
import gc
import itertools
import sys
import time
from typing import Dict, List

import numpy as np

from benchmark.harness import compare, loadgen

CHECK_STEPS = 3
SETTLE_STEPS = 2              # further warm steps before the window opens
IN_FLIGHT = 2                 # steps the host may run ahead of the device


def _say(msg: str) -> None:
    print(f"[train] {msg}", file=sys.stderr, flush=True)


def first_moment(updater_state: Dict) -> Dict:
    """Adam's first moment as a tree of the program's parameter names, from
    ``ComputationGraph``'s ``{vertex: {param: {"m", "v"}}}``."""
    return {v: {p: st["m"] for p, st in leaves.items()}
            for v, leaves in updater_state.items()}


class Session:
    """One net with its compiled step. ``run`` uses it for one seed; the
    calibration tool hands it new weights and a fresh state per seed."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.net, self.sizes, self.shapes = ctx.family.make_net(ctx.config)
        ctx.sizes = self.sizes
        traffic = ctx.traffic
        self.rows = int(traffic["batch_rows"])
        self.seq = int(traffic["seq_len"])
        self.staged: collections.deque = collections.deque()
        self.host_batches: List = []

    def install(self, seed: int) -> None:
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.ops.dataset import DataSet

        ctx, traffic = self.ctx, self.ctx.traffic
        ctx.family.install(self.net, ctx.config, self.sizes, self.shapes,
                           seed, train=True)
        self.host_batches = loadgen.train_batches(
            traffic, self.sizes["vocab"], seed,
            max(int(traffic.get("distinct_batches", 8)), CHECK_STEPS))
        source = itertools.cycle(self.host_batches)

        def stage():
            with ctx.span("bench.stage_batch"):
                x, y = next(source)
                return DataSet(jax.device_put(jnp.asarray(x)),
                               jax.device_put(jnp.asarray(y)))
        self._stage = stage
        self.staged = collections.deque(
            stage() for _ in range(int(traffic.get("staged_ahead", 2))))

    def step(self):
        ds = self.staged.popleft()
        self.staged.append(self._stage())
        with self.ctx.span("bench.fit_batch"):
            self.net.fit_batch(ds)
        return self.net.score_value

    def first_steps(self, seed: int) -> Dict:
        """Steps one to three by the window's own call and feed, with the
        readings the comparison needs."""
        family, sizes = self.ctx.family, self.sizes
        adam = self.ctx.config["run"]["optimizer"]
        losses = [self.step()]
        moment = family.canonical_view(
            first_moment(self.net.updater_state), sizes)
        grad = family.leaf_norms(moment) / (1.0 - float(adam["beta1"]))
        del moment
        losses += [self.step() for _ in range(CHECK_STEPS - 1)]
        change = family.change_norms(
            sizes, seed, family.canonical_view(self.net.params, sizes))
        return {"losses": [float(v) for v in losses], "grad_norms": grad,
                "change_norms": change}

    def release(self) -> None:
        """Free the program's state (the net and its compiled step stay)."""
        self.staged.clear()
        self.net.params = self.net.updater_state = None
        self.net.score_value = float("nan")
        gc.collect()


def run(ctx) -> Dict:
    import jax

    config, traffic, args = ctx.config, ctx.traffic, ctx.args
    adam = config["run"]["optimizer"]
    session = Session(ctx)
    session.install(args.seed)
    prog = session.first_steps(args.seed)
    for _ in range(SETTLE_STEPS):
        last = session.step()
    jax.block_until_ready(last)
    _say(f"first losses {prog['losses']}")

    # ---- the window
    tracer = ctx.tracer(args.seconds)
    setup_s = time.perf_counter() - ctx.t_start
    _say(f"set-up {setup_s:.1f}s; window opens")
    in_flight: collections.deque = collections.deque()
    steps = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < args.seconds:
        tracer.tick(time.perf_counter() - t0)
        in_flight.append(session.step())
        steps += 1
        if len(in_flight) > IN_FLIGHT:
            jax.block_until_ready(in_flight.popleft())
    with ctx.span("bench.readback"):
        final_loss = float(session.net.score_value)
    elapsed = time.perf_counter() - t0
    tracer.finish()
    memory_peak = ctx.memory_peak()
    ctx.train = {"steps": steps, "elapsed_s": elapsed,
                 "tokens_per_step": session.rows * session.seq,
                 "seq_len": session.seq, "final_loss": final_loss}

    # ---- free the program, then the reference follows the first steps
    host_batches, sizes = session.host_batches, session.sizes
    del in_flight, last
    session.release()
    t_ref = time.perf_counter()
    ref = ctx.family.train_steps(sizes, args.seed,
                                 host_batches[:CHECK_STEPS], adam,
                                 rows_per_block=int(
                                     traffic.get("reference_rows", 2)))
    _say(f"reference, {CHECK_STEPS} steps: "
         f"{time.perf_counter() - t_ref:.1f}s; losses {ref['losses']}")
    numbers = numbers_of(prog, ref, ctx.family.flat_names(sizes))
    numbers["final_loss_finite"] = 0.0 if np.isfinite(final_loss) else 1.0
    return {"numbers": numbers, "attempted": steps, "failed": 0,
            "setup_s": setup_s, "memory_peak_bytes": memory_peak}


def numbers_of(prog: Dict, ref: Dict, names: List[str]
               ) -> Dict[str, float]:
    """The numbers a training cell compares: the program's (or a control's)
    ``losses``, ``grad_norms`` and ``change_norms`` against the reference's,
    the per-leaf vectors in the order of ``names``."""
    out = {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss_gap.{i + 1}"] = abs(lp - lr) / abs(lr)
    out["grad_norm_gap"], gi = compare.worst_leaf_gap(prog["grad_norms"],
                                                      ref["grad_norms"])
    counted = compare.moved_leaves(ref["grad_norms"])
    out["change_norm_gap"], ci = compare.worst_leaf_gap(
        prog["change_norms"], ref["change_norms"], counted)
    _say(f"worst leaves: gradient {names[gi]}, change {names[ci]}; "
         f"{int((~counted).sum())} leaves not counted in the change")
    return out


def end_to_end(ctx, name: str):
    if name == "train_tokens_per_s":
        t = ctx.train
        return t["steps"] * t["tokens_per_step"] / t["elapsed_s"]
    return None


# ------------------------------------------- readings for calibrate.py
def calibrate(ctx, seeds, seconds, n_control, n_fault, limits, control):
    """Per seed the program's numbers from one compiled step; on the first
    ``n_control`` seeds also the control (the reference in the precision
    ``control``, put in the program's place), on the last ``n_fault`` seeds
    half of the batch left out. No window: training's readings need none.
    One line (a dict) per seed."""
    del seconds
    session = Session(ctx)
    family, sizes = ctx.family, session.sizes
    adam = ctx.config["run"]["optimizer"]
    rows = int(ctx.traffic.get("reference_rows", 2))
    names = family.flat_names(sizes)
    for i, seed in enumerate(seeds):
        session.install(seed)
        prog = session.first_steps(seed)
        batches = session.host_batches[:CHECK_STEPS]
        session.release()
        ref = family.train_steps(sizes, seed, batches, adam,
                                 rows_per_block=rows)
        ref.pop("params")
        out = {"seed": seed, "numbers": numbers_of(prog, ref, names)}
        out["verdict"] = {"program": compare.verdict(limits, out["numbers"])}
        if i < n_control:
            low = family.train_steps(sizes, seed, batches, adam,
                                     precision=control, rows_per_block=rows)
            out["control"] = numbers_of(low, ref, names)
            out["verdict"]["control"] = compare.verdict(
                limits, out["numbers"], out["control"])
        if i >= len(seeds) - n_fault:
            half = list(range(session.rows // 2))
            low = family.train_steps(sizes, seed, batches, adam,
                                     rows_per_block=rows, keep=half)
            out["fault_half_batch"] = numbers_of(low, ref, names)
            out["verdict"]["half_batch"] = compare.verdict(
                limits, out["numbers"], out["fault_half_batch"])
        yield out
