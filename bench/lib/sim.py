"""The simulated federation on one chip: ``repro.core.make_federated_step``.

The program under test is the jitted round of ``make_federated_step`` over
the configuration's data, compiled once by
``repro.launch.steps.compile_train_step`` (state donated).  Its reference
is :class:`SimReference`: the same round written plainly, vectorised over
workers, in float32 (or, for the control, in the configuration's
``CONTROL_DTYPE``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib import reference as ref_lib
from bench.lib.check import leaf_norms
from bench.lib.seeds import seed_key


class SimReference:
    """One plain Byrd-SAGA round per :meth:`step` (Alg. 1 with the attack
    rows appended after the honest ones, as the simulated federation
    orders them).  ``variant``: ``f32``; ``control`` (the configuration's
    lower precision); the faults ``unchanged`` (the state never moves) and
    ``half_batch`` (half of the honest messages left out, the rule run on
    the rest)."""

    def __init__(self, cell, mod, data, params0, fed_key, variant="f32"):
        cfg, t = cell["config"], cell["traffic"]
        self.cfg, self.t, self.variant = cfg, t, variant
        self.dtype = mod.CONTROL_DTYPE if variant == "control" else jnp.float32
        dt = self.dtype
        self.data = jax.tree_util.tree_map(lambda x: x.astype(dt), data)
        self.params = jax.tree_util.tree_map(lambda x: x.astype(dt), params0)
        self.key = fed_key
        self.lr = cfg["lr"]
        self.wh, self.j = self.data["b"].shape[:2]

        def one(p, a, b):
            return mod.ref_loss(p, {"a": a[None], "b": b[None]}, cfg, dt)

        grad_one = jax.grad(one)
        self._grads = jax.jit(jax.vmap(grad_one, in_axes=(None, 0, 0)))
        # Alg. 1: the table starts from every row's gradient at x^0.
        table = jax.jit(jax.vmap(jax.vmap(grad_one, in_axes=(None, 0, 0)),
                                 in_axes=(None, 0, 0)))(
            self.params, self.data["a"], self.data["b"])
        self.table = table
        self.avg = jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=1), table)

    def step(self):
        """One round; returns the aggregate the optimizer got (a pytree)."""
        self.key, k_idx, _ = jax.random.split(self.key, 3)
        idx = jax.random.randint(k_idx, (self.wh,), 0, self.j)
        rows = jnp.arange(self.wh)
        a = self.data["a"][rows, idx]
        b = self.data["b"][rows, idx]
        g = self._grads(self.params, a, b)
        old = jax.tree_util.tree_map(lambda t: t[rows, idx], self.table)
        msg = jax.tree_util.tree_map(lambda gg, o, m: gg - o + m,
                                     g, old, self.avg)
        self.avg = jax.tree_util.tree_map(
            lambda m, gg, o: m + (gg - o) / self.j, self.avg, g, old)
        self.table = jax.tree_util.tree_map(
            lambda t, gg: t.at[rows, idx].set(gg), self.table, g)
        honest = jnp.concatenate(
            [x.reshape(self.wh, -1) for x in jax.tree_util.tree_leaves(msg)],
            axis=1).astype(self.dtype)
        if self.variant == "half_batch":
            honest = honest[: self.wh // 2]
        points = honest
        if self.t["byzantine"]:
            byz = ref_lib.sign_flip(jnp.mean(honest, axis=0))
            points = jnp.concatenate(
                [honest, jnp.broadcast_to(byz, (self.t["byzantine"],)
                                          + byz.shape)], axis=0)
        agg_vec = ref_lib.aggregate(points, self.t["aggregator"],
                                    max_iters=self.t["weiszfeld_iters"],
                                    tol=self.t["weiszfeld_tol"])
        agg = ref_lib.vec_tree(agg_vec.astype(self.dtype), self.params)
        if self.variant != "unchanged":
            self.params = jax.tree_util.tree_map(
                lambda p, gg: (p - self.lr * gg).astype(self.dtype),
                self.params, agg)
        return agg


class SimCell:
    """Builds the program's round for one seed and drives it."""

    def __init__(self, cell, seed: int, devices, impl: str = "program"):
        from bench.lib.cells import config_module
        self.cell, self.seed, self.devices, self.impl = cell, seed, devices, impl
        self.cfg, self.t = cell["config"], cell["traffic"]
        self.mod = config_module(self.cfg)
        self.steps = self.t["check_steps"]
        self.state = None
        self.jstep = None

    # -- set-up ----------------------------------------------------------
    def setup(self):
        key = seed_key(self.seed)
        with jax.default_device(self.devices[0]):
            data = self.mod.make_data(jax.random.fold_in(key, 0), self.cfg)
            self.worker_data = self.mod.split(data, self.cfg, self.seed)
            self.params0 = self.mod.init_params(self.cfg)
            self.fed_key = jax.random.fold_in(key, 1)
            flat = jax.tree_util.tree_map(
                lambda x: x.reshape((-1,) + x.shape[2:]), self.worker_data)
            self._full_loss = jax.jit(lambda p: self.mod.ref_loss(
                p, flat, self.cfg, jnp.float32))
            if self.impl == "program":
                self._build_program()
            else:
                self.stepper = SimReference(
                    self.cell, self.mod, self.worker_data, self.params0,
                    self.fed_key, variant=self.impl.split(":", 1)[1])

    def _build_program(self):
        from repro.core import RobustConfig, make_federated_step
        from repro.launch.steps import compile_train_step
        from repro.optim import get_optimizer
        t = self.t
        robust = RobustConfig(
            aggregator=t["aggregator"], vr=t["vr"], attack=t["attack"],
            num_byzantine=t["byzantine"],
            weiszfeld_iters=t["weiszfeld_iters"],
            weiszfeld_tol=t["weiszfeld_tol"])
        init_fn, step_fn = make_federated_step(
            self.mod.program_loss(self.cfg), self.worker_data, robust,
            get_optimizer(t["optimizer"], self.cfg["lr"]))
        self.state = jax.jit(init_fn)(self.params0, self.fed_key)
        self.jstep = compile_train_step(step_fn)

    def _params(self):
        if self.impl == "program":
            return self.state.params
        return self.stepper.params

    def first_steps(self) -> dict:
        """Drive the round ``check_steps`` times through the window's own
        call; the readings the reference is compared on."""
        w0 = self.params0
        params, first_grad = [], None
        for k in range(self.steps):
            h = self.round()
            p = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                       self._params())
            if k == 0:
                first_grad = jax.tree_util.tree_map(
                    lambda a, b: (a - b) / self.cfg["lr"], w0, p)
            params.append(p)
        jax.block_until_ready(h)
        return self._readings(w0, params, first_grad)

    def _readings(self, w0, params, first_grad) -> dict:
        with jax.default_matmul_precision("highest"):
            loss = [float(self._full_loss(jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32), p))) for p in params]
        change = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            params[-1], w0)
        return {"loss": loss, "grad": leaf_norms(first_grad),
                "change": leaf_norms(change)}

    # -- the timed path --------------------------------------------------
    def round(self):
        """Dispatch one round; returns something to wait on."""
        if self.impl == "program":
            self.state, metrics = self.jstep(self.state)
            return metrics
        return self.stepper.step()

    def next_input(self):
        return None

    def hlo_text(self):
        """The compiled step's HLO (a cache hit of the step's compile)."""
        if self.jstep is None:
            return None
        return self.jstep.lower(self.state).compile().as_text()

    def flops_per_round(self):
        return self.mod.flops_per_round(self.cfg, self.t)

    def release(self):
        self.state = None
        self.jstep = None

    # -- the reference ---------------------------------------------------
    def reference(self, variant: str = "f32") -> dict:
        with jax.default_device(self.devices[0]), \
                jax.default_matmul_precision("highest"):
            r = SimReference(self.cell, self.mod, self.worker_data,
                             self.params0, self.fed_key, variant=variant)
            params, first = [], None
            for k in range(self.steps):
                agg = r.step()
                if k == 0:
                    first = agg
                params.append(r.params)
            return self._readings(self.params0, params, first)
