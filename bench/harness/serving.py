"""Set-up and the two drivers of a serving cell.

Set-up makes the weights on the device from the seed in one jitted call,
builds the program's ``BatchServer`` for the configuration, and warms up
exactly the prefill buckets the cell's traffic uses plus its decode program,
through the same ``submit``/``step`` calls the window makes. What the model's
family decides (its ``ModelConfig``, its work counts, initializers of its
own leaves) comes from the family module the configuration names. A cell on
more than one chip serves on a ``("data", "model")`` mesh of ``(1, chips)``:
the weights are made sharded by the program's rules, so that no chip holds
the whole model, and the server runs tensor-parallel. The window is
then driven open loop (requests submitted at their due times, which a stall
does not move) or as an offline backlog (the queue never empties; every slot
is filled before the window opens, so the window measures the steady state
and not the filling of empty slots). Every
call into the server runs inside a ``jax.profiler.TraceAnnotation`` named
``bench.<what>``, so a trace can say what the host was doing in each gap of
the device.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from harness import traffic as traffic_mod
from harness.spec import Cell

CLOCK = time.perf_counter
MIN_BUCKET = 4          # the server's smallest prompt bucket
DRAIN_LIMIT_S = 60.0    # how long the requests of a closed open-loop window
                        # may take
STALL_STEPS = 3         # steps after a backlog window in which every
                        # request still in a slot has to gain a token


def bucket_len(n: int, max_len: int) -> int:
    """The prompt bucket the server pads a prompt of ``n`` tokens to."""
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return min(b, max_len)


def buckets(mix: dict, max_len: int) -> List[int]:
    """The prompt buckets a traffic mix fills."""
    return sorted({bucket_len(int(n), max_len)
                   for n in traffic_mod.prompt_lengths(mix)})


def seed_key(seed: int) -> np.ndarray:
    """A raw threefry key from a seed of any size."""
    return np.random.SeedSequence(seed).generate_state(2).astype(np.uint32)


class CompileClock:
    """Counts JAX's backend compiles (or persistent-cache fetches) and sums
    their seconds."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs


def init_weights(model, seed: int, initializers=None, mesh=None):
    """Random weights in the program's parameter layout, made from the seed
    by one jitted call (:func:`weights_program`) on the device."""
    return weights_program(model, initializers, mesh)(seed_key(seed))


def weights_program(model, initializers=None, mesh=None):
    """The jitted call that makes the weights from a raw key, in the dtype
    they are served in: projections N(0, 1/fan_in), embeddings N(0, 0.02^2),
    norm scales 1 + N(0, 0.1^2), and any other leaf by its family's
    ``initializers`` ({leaf name: fn(key, shape, dtype)}). With a ``mesh``
    every leaf is made where the program's sharding rules place it; the
    values do not depend on that."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    initializers = initializers or {}

    def make(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            name = path[-1].key
            if name == "w":
                x = jax.random.normal(k, s.shape, s.dtype) \
                    * (1.0 / s.shape[-2] ** 0.5)
            elif name == "table":
                x = jax.random.normal(k, s.shape, s.dtype) * 0.02
            elif name == "scale":
                x = 1.0 + 0.1 * jax.random.normal(k, s.shape, jnp.float32)
            elif name == "b":
                x = jnp.zeros(s.shape, jnp.float32)
            elif name in initializers:
                x = initializers[name](k, s.shape, s.dtype)
            else:
                raise ValueError(f"no initializer for parameter {path}")
            out.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    if mesh is None:
        return jax.jit(make)
    from repro.dist import sharding
    return jax.jit(make, out_shardings=sharding.to_named(
        sharding.param_specs(shapes, mesh), mesh))


def make_mesh(devices, chips: int):
    """The ``("data", "model")`` mesh of ``(1, chips)`` over the first
    ``chips`` of ``devices``, or None for a cell on one chip."""
    if chips == 1:
        return None
    import jax
    from jax.sharding import Mesh
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < chips:
        raise ValueError(f"the cell needs {chips} chips, {len(devices)} "
                         f"given")
    return Mesh(np.array(devices[:chips]).reshape(1, chips),
                ("data", "model"))


@dataclasses.dataclass
class Record:
    """What the harness saw of one request."""
    rid: int
    due: float
    n_prompt: int
    max_new: int
    prompt: np.ndarray
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    out: Optional[List[int]] = None
    error: str = ""


@dataclasses.dataclass
class Step:
    """One ``step`` call: its host interval, the tokens it emitted, its
    prefill dispatches and its decode work."""
    index: int
    t0: float
    t1: float
    tokens: int = 0
    prefills: list = dataclasses.field(default_factory=list)
    # each: {"bucket", "rows": useful prompt tokens, "requests", "flops"}
    decode_tokens: int = 0
    decode_ctx_rows: int = 0       # live K/V rows the decoded tokens attend
    decode_flops: int = 0


class Session:
    """One cell: the model, its weights for a seed, and a warm server."""

    def __init__(self, cell: Cell, devices=None):
        self.cell = cell
        self.serving = cell.config["serving"]
        self.tier = cell.tier
        fam = cell.family
        self.shapes = fam.ModelShapes.from_model(cell.config["model"])
        self.model_cfg = fam.model_config(cell.config)
        self.mesh = make_mesh(devices, cell.chips)
        from repro.models.model import build_model
        self.model = build_model(self.model_cfg)
        self.slots = int(self.serving["slots"])
        self.max_len = int(self.serving["max_len"])
        self.compiles = CompileClock()
        self.params = None
        self.server = None
        self.runs = 0              # windows driven: each takes fresh rids

    # -- set-up ---------------------------------------------------------------
    def make_weights(self, seed: int) -> None:
        import jax
        self.params = None
        gc.collect()
        self.params = init_weights(self.model, seed,
                                   self.cell.family.INITIALIZERS, self.mesh)
        jax.block_until_ready(self.params)

    def build_server(self) -> None:
        from repro.serve.batcher import BatchServer
        self.server = BatchServer(
            self.model, batch_slots=self.slots, max_len=self.max_len,
            quantized=self.tier == "int8", mesh=self.mesh, clock=CLOCK)

    def buckets(self) -> List[int]:
        return buckets(self.cell.traffic, self.max_len)

    def warm_up(self) -> None:
        """Compile (or fetch from the persistent cache) every program the
        window will run: one prefill per bucket of the traffic, and decode."""
        from repro.serve.batcher import Request
        import jax
        srv = self.server
        rng = np.random.default_rng(0)
        for i, b in enumerate(self.buckets()):
            n = min(b, self.max_len - 1)      # a prompt that fills bucket b
            with jax.profiler.TraceAnnotation("bench.submit"):
                srv.submit(Request(rid=-1 - i, prompt=rng.integers(
                    0, self.shapes.vocab, n, dtype=np.int32),
                    max_new_tokens=2))
        while srv.has_queued() or any(s.req is not None for s in srv.slots):
            with jax.profiler.TraceAnnotation("bench.warmup"):
                srv.step(self.params)
        srv.take_completed()

    def free_server(self) -> None:
        self.server = None
        gc.collect()

    # -- the window -------------------------------------------------------------
    def run(self, seed: int, seconds: float, trace_dir=None) -> dict:
        """Drive the warm server for ``seconds`` with the cell's traffic from
        ``seed``, then drain it; with ``trace_dir`` take a profiler trace of
        the window's last ``trace_seconds``. A backlog first fills every slot
        (``fill_s``). Returns the window's bounds and compiles, its ``Record``
        of every request attempted and its ``Step`` of every ``step`` call,
        the fill's and the drain's included."""
        from repro.serve.batcher import Request
        import jax
        srv = self.server
        mix = self.cell.traffic
        backlog = mix["arrival"]["kind"] == "backlog"
        stream = traffic_mod.requests(mix, seed, self.shapes.vocab)
        rid0 = self.runs * 10 ** 9      # the server remembers finished rids
        self.runs += 1
        records: Dict[int, Record] = {}
        inflight: Dict[int, tuple] = {}     # rid -> (Record, Request, count)
        steps: List[Step] = []
        compiles0 = (self.compiles.count, dict(srv.compiles))
        tracing = False

        def submit(plan, due_t):
            rec = Record(rid=rid0 + plan.index, due=due_t, n_prompt=len(plan.prompt),
                         max_new=plan.max_new, prompt=plan.prompt)
            req = Request(rid=rec.rid, prompt=plan.prompt,
                          max_new_tokens=plan.max_new)
            rec.t_submit = CLOCK()
            try:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    srv.submit(req)
            except Exception as e:          # refused: counts as failed
                rec.error = f"{type(e).__name__}: {e}"
            records[rec.rid] = rec
            if not rec.error:
                inflight[rec.rid] = (rec, req, 0)

        def step():
            k = len(steps)
            st = Step(index=k, t0=CLOCK(), t1=0.0)
            with jax.profiler.TraceAnnotation(f"bench.step:{k}"):
                srv.step(self.params)
            st.t1 = CLOCK()
            self._account(st, inflight)
            steps.append(st)

        def waiting() -> int:
            return sum(1 for rec, _, _ in inflight.values() if not rec.t_first)

        nxt = next(stream)
        t_fill = CLOCK()
        if backlog:
            for _ in range(self.slots):
                submit(nxt, t_fill)
                nxt = next(stream)
            step()                      # admits into every slot
        t0 = CLOCK()
        due = t0 + nxt.gap_s
        deadline = t0 + seconds
        # the trace takes the window's last seconds, so that stopping it,
        # which stalls the host for seconds, falls after the window
        trace_at = None if trace_dir is None else max(
            t0, deadline - float(mix["trace_seconds"]))

        while True:
            now = CLOCK()
            if now >= deadline:
                break
            if trace_at is not None and not tracing and now >= trace_at:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
                tracing = True
            if backlog:
                while waiting() < self.slots:
                    submit(nxt, CLOCK())
                    nxt = next(stream)
            else:
                while due <= now:
                    submit(nxt, due)
                    nxt = next(stream)
                    due += nxt.gap_s
            if inflight:
                step()
            else:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(due, deadline) - CLOCK()))
        t_stop = CLOCK()
        if tracing:
            jax.profiler.stop_trace()
        window_compiles = (self.compiles.count - compiles0[0]
                           + sum(srv.compiles.values())
                           - sum(compiles0[1].values()))

        # close. Open loop submits what fell due during the last step and
        # drains what is in flight, within a limit: it owes an answer to
        # every request due in the window, so one left unfinished has
        # failed. An offline backlog withdraws what it never admitted, then
        # runs a few steps, in each of which every request in a slot gains
        # a token: one that gains none is stuck and has failed; the others
        # were cut off by the end of the run and are withdrawn.
        if backlog:
            for rid in [r for r in inflight
                        if srv.request_phase(r) == "queued"]:
                srv.abort(rid)
                inflight.pop(rid)
                del records[rid]
            start = {rid: len(req.out_tokens)
                     for rid, (_, req, _) in inflight.items()}
            for _ in range(STALL_STEPS):
                if inflight:
                    step()
            for rid, (rec, req, _) in inflight.items():
                srv.abort(rid)
                if len(req.out_tokens) > start[rid]:
                    del records[rid]
                else:
                    rec.error = (f"no token in the {STALL_STEPS} steps "
                                 f"after the window")
        else:
            while due <= t_stop:
                submit(nxt, due)
                nxt = next(stream)
                due += nxt.gap_s
            drain_end = CLOCK() + DRAIN_LIMIT_S
            while inflight and CLOCK() < drain_end:
                step()
            for rec, _, _ in inflight.values():
                rec.error = rec.error or "not completed within the drain limit"
        srv.take_completed()
        return {"t0": t0, "t_stop": t_stop, "seconds": t_stop - t0,
                "fill_s": t0 - t_fill,
                "records": list(records.values()), "steps": steps,
                "window_compiles": window_compiles, "backlog": backlog}

    def _account(self, st: Step, inflight: Dict[int, tuple]) -> None:
        """Fold one step's effects into its record: tokens each in-flight
        request gained, the decode work they cost, and the prefill
        dispatches the server's own ``prefill`` spans report."""
        shapes = self.shapes
        n_prompt = {rid: rec.n_prompt for rid, (rec, _, _) in inflight.items()}
        for rid, (rec, req, before) in list(inflight.items()):
            after = len(req.out_tokens)
            st.tokens += after - before
            if after:
                prev = max(before, 1)
                for j in range(after - prev):
                    ctx = rec.n_prompt + prev + j
                    st.decode_tokens += 1
                    st.decode_ctx_rows += ctx
                    st.decode_flops += shapes.decode_flops(ctx)
            if req.t_first and not rec.t_first:
                rec.t_first = req.t_first
            if req.t_done:
                rec.t_done = req.t_done
                rec.out = list(req.out_tokens)
                inflight.pop(rid)
            else:
                inflight[rid] = (rec, req, after)
        # the tracer keeps spans in the order they ended: walk back to the
        # first one that ended before this step began
        spans = []
        for s in reversed(self.server.tracer.spans):
            if s.t1 < st.t0:
                break
            if s.name == "prefill" and s.t0 >= st.t0:
                spans.append(s)
        for s in reversed(spans):
            rows = [n_prompt[r] for r in s.attrs["rids"]]
            st.prefills.append({
                "bucket": int(s.attrs["bucket"]), "rows": sum(rows),
                "requests": len(rows),
                "flops": sum(shapes.prefill_flops(n) for n in rows)})
