"""The system under test, built from a cell's files: the program's
RoundEngine with its QSR schedule, fed through its host-data hook
(`batch_fn`) from the benchmark's pool.

Everything the benchmark takes from the program passes through here: the
engine and its `run_round`, `compile_stats` and flat-layout `spec`, the
schedule's `get_h` and learning rate.

A cell without a `mesh` in its traffic runs its workers as lanes on one
chip.  A cell with one builds that mesh of chips through the program's
`launch/mesh.make_mesh` and hands it, with the traffic's sharding policy,
to the engine, which lays the worker-stacked state out over the mesh's
worker axis itself (from one worker's state, built on the host); the
weights are made replicated over the mesh and the input pool split over
its worker axis, so that each chip's batch is made on that chip.
"""
from __future__ import annotations

import jax

from bench import inputs
from bench.compare import by_leaf, lead_change_norms, lead_norms
from bench.reference import common as C


class System:
    def __init__(self, cell):
        from repro.configs.base import ModelConfig, RunConfig
        from repro.core import schedules
        from repro.core.engine import RoundEngine
        from repro.optim.lr import make_lr_fn

        self.cell = cell
        conf, traffic = cell.conf, cell.traffic
        sched, opt = traffic["schedule"], traffic["optimizer"]
        self.kind = cell.model.INPUT
        self.workers = traffic["workers"]
        self.b_loc = traffic["batch_per_worker"]
        self.tokens_per_example = inputs.tokens_per_example(
            self.kind, conf, traffic)
        self.t0 = traffic["start_step"]
        self.cfg = ModelConfig(**cell.model.program_kwargs(conf))
        self.run_cfg = RunConfig(
            schedule=sched["rule"], optimizer=opt["name"],
            h_base=sched["h_base"], alpha=sched["alpha"],
            peak_lr=sched["peak_lr"], end_lr=sched["end_lr"],
            warmup_steps=sched["warmup_steps"],
            total_steps=sched["total_steps"],
            lr_schedule=sched["lr_schedule"],
            weight_decay=opt["weight_decay"], remat=traffic["remat"])
        self.lr_fn = make_lr_fn(self.run_cfg)
        self.get_h = lambda t: schedules.get_h(self.run_cfg, t, self.lr_fn)
        mesh, self.mesh, engine_kw = traffic.get("mesh"), None, {}
        if mesh:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.launch.mesh import make_mesh
            from repro.models import param as pm
            self.mesh = make_mesh(mesh["shape"], mesh["axes"])
            engine_kw = {"mesh": self.mesh, "policy": mesh["policy"]}
            self.devices = list(self.mesh.devices.flat)
            self._replicated = NamedSharding(self.mesh, P())
            self._by_worker = NamedSharding(self.mesh, P(
                pm.worker_mesh_axes(mesh["policy"], self.mesh)))
        else:
            self.devices = jax.devices()[:1]
        self.pool = None

        def batch_fn(step):
            with jax.profiler.TraceAnnotation("bench.batch_fetch"):
                return self.pool[(step - self.t0) % len(self.pool)]

        self._grad = jax.jit(lambda m: lead_norms(self._tree(m)))
        self._change = jax.jit(
            lambda p, p0: lead_change_norms(self._tree(p), p0))
        self.eng = RoundEngine(
            self.cfg, self.run_cfg, workers=self.workers, b_loc=self.b_loc,
            seq=self.tokens_per_example, data="host", batch_fn=batch_fn,
            layout=traffic["layout"], **engine_kw)

    # -- inputs and state --------------------------------------------------

    def params(self, seed: int):
        """The initial weights, made on the device in one jitted call;
        on a mesh, replicated over its chips."""
        shapes = self.cell.model.param_shapes(self.cell.conf)
        kw = {"out_shardings": self._replicated} if self.mesh else {}
        return jax.jit(lambda key: C.init_params(shapes, key), **kw)(
            C.seed_key(seed, C.WEIGHTS))

    def init(self, seed: int):
        """Engine state and input pool for `seed`."""
        self.pool = inputs.make_pool(
            self.kind, self.cell.conf, self.cell.traffic, self.workers, seed,
            sharding=self._by_worker if self.mesh else None)
        if self.mesh:
            # the engine lays one worker's state out over the mesh through
            # the host (flat.make_global), after building that state with
            # eager ops on the default device: on a chip, its transient
            # copies set an allocator peak that hangs on timing.  So the
            # state is built on the host's CPU, and no copy of it passes
            # through a chip before its shards
            weights = jax.device_get(self.params(seed))
            with jax.default_device(jax.devices("cpu")[0]):
                state = self.eng.init_state(weights)
        else:
            shapes = self.cell.model.param_shapes(self.cell.conf)
            state = jax.jit(lambda key: self.eng.init_state(
                C.init_params(shapes, key)))(C.seed_key(seed, C.WEIGHTS))
        return jax.block_until_ready(state)

    def release(self):
        self.pool = None

    # -- readings of the program's state -----------------------------------

    def _tree(self, bufs):
        eng = self.eng
        return bufs if eng.layout == "tree" else eng.spec.unflatten(bufs,
                                                                   lead=1)

    def grad_norms(self, state) -> dict:
        """{leaf: [W]} norms of every worker's first moment."""
        return by_leaf(self._paths(), self._grad(state["opt"]["m"]))

    def change_norms(self, state, seed: int) -> dict:
        """{leaf: [W]} norms of every worker's change from the initial
        weights, which are made again from the seed."""
        return by_leaf(self._paths(), self._change(
            state["params"], self.params(seed)))

    def _paths(self) -> list[str]:
        return C.shape_paths(self.cell.model.param_shapes(self.cell.conf))

