"""The mesh path: ``repro.launch.steps.make_train_step``, one worker per
index of the mesh's data axis, as ``repro.launch.train`` runs it.

The program under test is the step compiled once by
``repro.launch.steps.compile_train_step`` (state donated), with the state
placed by the step's own ``state_specs``.  Its reference is
:class:`MeshReference`: the same round written plainly, worker by worker,
each worker's gradient and SAGA table on a device of its own.
"""
from __future__ import annotations

import contextlib
import json

import jax
import jax.numpy as jnp

from bench.lib import reference as ref_lib
from bench.lib.check import leaf_norms
from bench.lib.seeds import seed_key

B1 = 0.9   # Adam's first-moment decay, program and reference alike


_COMPILED = {}


def _compiled(mod, cfg: dict, policy: str, j: int, opt):
    """The reference's jitted pieces, shared by every reference of one
    configuration, precision and optimizer in this process."""
    key = (json.dumps(cfg, sort_keys=True), policy, j, type(opt).__name__,
           json.dumps({k: v for k, v in vars(opt).items()}, sort_keys=True))
    if key in _COMPILED:
        return _COMPILED[key]

    def loss(p, tok, lab):
        p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)
        with jax.default_matmul_precision("highest"):
            return mod.ref_loss(p32, tok, lab, cfg, policy)

    def saga(table, avg, g, i):
        old = jax.tree_util.tree_map(lambda t: t[i], table)
        msg = jax.tree_util.tree_map(lambda gg, o, m: gg - o + m, g, old, avg)
        avg = jax.tree_util.tree_map(lambda m, gg, o: m + (gg - o) / j,
                                     avg, g, old)
        table = jax.tree_util.tree_map(lambda t, gg: t.at[i].set(gg), table, g)
        return table, avg, ref_lib.tree_vec(msg)

    fns = (jax.jit(jax.value_and_grad(loss)),
           jax.jit(saga, donate_argnums=(0, 1)), jax.jit(opt.update))
    _COMPILED[key] = fns
    return fns


class MeshReference:
    """One plain round per :meth:`step`: each worker's float32 loss and
    gradient, its SAGA correction against a zero-started (J, ...) table, the
    first B messages replaced by the attack, the rule over all W, AdamW.
    ``variant``: ``f32``; ``control`` (the configuration's lower precision);
    the faults ``unchanged``, ``half_batch`` (each worker's loss over the
    first half of its rows) and ``no_exchange`` (the rule sees only the last
    worker's own message)."""

    def __init__(self, cell, mod, params0, batches, keys, devices,
                 variant="f32"):
        self.cfg, self.t, self.mod = cell["config"], cell["traffic"], mod
        self.variant, self.devices = variant, devices
        self.batches, self.keys = batches, keys
        self.w = self.t["mesh"][0]
        self.j = self.t["saga_samples"]
        self.params = params0
        self.opt = ref_lib.optimizer(self.t["optimizer"], self.t["lr"])
        self.opt_state = self.opt.init(params0)
        self.k = 0
        policy = mod.CONTROL if variant == "control" else "f32"
        self._vg, self._saga, self._update = _compiled(
            mod, self.cfg, policy, self.j, self.opt)
        zero = lambda x: jnp.zeros((self.j,) + x.shape, jnp.float32)
        self.tables, self.avgs = [], []
        for i in range(self.w):
            dev = self.devices[i % len(self.devices)]
            self.tables.append(jax.device_put(
                jax.tree_util.tree_map(zero, params0), dev))
            self.avgs.append(jax.device_put(jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), params0), dev))

    def step(self):
        """One round; returns (the aggregate the optimizer got, the loss)."""
        batch, key = self.batches(self.k), self.keys(self.k)
        self.k += 1
        idx = jax.random.randint(jax.random.fold_in(key, 1), (self.w,), 0,
                                 self.j)
        msgs, losses = [], []
        for i in range(self.w):
            dev = self.devices[i % len(self.devices)]
            tok, lab = batch["tokens"][i], batch["labels"][i]
            if self.variant == "half_batch":
                tok, lab = tok[: tok.shape[0] // 2], lab[: lab.shape[0] // 2]
            p = jax.device_put(self.params, dev)
            lv, g = self._vg(p, jax.device_put(tok, dev),
                             jax.device_put(lab, dev))
            self.tables[i], self.avgs[i], m = self._saga(
                self.tables[i], self.avgs[i], g, jax.device_put(idx[i], dev))
            msgs.append(jax.device_put(m, self.devices[0]))
            losses.append(float(lv))
            del p, g
        points = jnp.stack(msgs)
        del msgs
        b = self.t["byzantine"] if self.t["attack"] != "none" else 0
        if b:
            byz = ref_lib.sign_flip(jnp.mean(points[b:], axis=0))
            points = points.at[:b].set(byz)
        if self.variant == "no_exchange":
            agg_vec = points[-1]
        else:
            agg_vec = ref_lib.aggregate(
                points, self.t["aggregator"],
                max_iters=self.t["weiszfeld_iters"],
                tol=self.t["weiszfeld_tol"])
        del points
        agg = ref_lib.vec_tree(agg_vec, self.params)
        if self.variant != "unchanged":
            self.params, self.opt_state = self._update(self.params, agg,
                                                       self.opt_state)
        return agg, sum(losses) / len(losses)


class MeshCell:
    """Builds the program's mesh step for one seed and drives it."""

    def __init__(self, cell, seed: int, devices, impl: str = "program"):
        from bench.lib.cells import config_module
        self.cell, self.seed, self.devices, self.impl = cell, seed, devices, impl
        self.cfg, self.t = cell["config"], cell["traffic"]
        self.mod = config_module(self.cfg)
        self.steps = self.t["check_steps"]
        self.w = self.t["mesh"][0]
        self.i = 0
        self.state = self.jstep = None
        self._ctx = contextlib.ExitStack()

    # -- inputs, the same for the program and the reference --------------
    def _keys(self):
        key = seed_key(self.seed)
        return (jax.random.fold_in(key, 0), jax.random.fold_in(key, 1),
                jax.random.fold_in(key, 2))

    def batch_key(self, i):
        return jax.random.fold_in(self._kb, i)

    def step_key(self, i):
        return jax.random.fold_in(self._ks, i)

    # -- set-up ----------------------------------------------------------
    def setup(self):
        from repro import compat
        t = self.t
        self._kp, self._kb, self._ks = self._keys()
        self.mesh = compat.make_mesh(tuple(t["mesh"]), ("data", "model"),
                                     devices=self.devices)
        if self.impl == "program":
            self._build_program()
        else:
            self.stepper = self._reference(self.impl.split(":", 1)[1])

    def _gen_batch(self, sharding=None):
        t, cfg, mod = self.t, self.cfg, self.mod
        return jax.jit(lambda k: mod.make_batch(k, cfg, self.w,
                                                t["per_worker_batch"],
                                                t["seq"]),
                       out_shardings=sharding)

    def _build_program(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.launch import sharding as shard_lib
        from repro.launch import steps as steps_lib
        from repro.models.api import build_model
        from repro.optim import get_optimizer
        t, cfg = self.t, self.cfg
        mcfg = self.mod.model_config(cfg)
        seq = t["seq"]
        model = build_model(mcfg, remat=False, q_chunk=min(seq, 512),
                            kv_chunk=min(seq, 512), loss_chunk=128)
        robust = RobustConfig(
            aggregator=t["aggregator"], vr=t["vr"], attack=t["attack"],
            num_byzantine=t["byzantine"], comm=t["comm"],
            weiszfeld_iters=t["weiszfeld_iters"],
            weiszfeld_tol=t["weiszfeld_tol"],
            message_dtype=t["message_dtype"])
        train = TrainConfig(optimizer=t["optimizer"], lr=t["lr"])
        fn, specs, _ = steps_lib.make_train_step(
            model, robust, train, self.mesh, saga_num_samples=t["saga_samples"])
        shardings = shard_lib.named(self.mesh, specs)
        structs = model.param_structs()
        opt = get_optimizer(t["optimizer"], t["lr"])
        reducer = robust.reducer()
        j = t["saga_samples"]

        def make_state(k):
            params = self.mod.init_params(k, cfg)
            return {"params": params, "opt": opt.init(params),
                    "step": jnp.zeros((), jnp.int32),
                    "vr": reducer.init_zeros(params, self.w, j)}

        shapes = jax.eval_shape(make_state, self._kp)["params"]
        if jax.tree_util.tree_structure(shapes) != \
                jax.tree_util.tree_structure(structs) or any(
                    a.shape != b.shape or a.dtype != b.dtype for a, b in zip(
                        jax.tree_util.tree_leaves(shapes),
                        jax.tree_util.tree_leaves(structs))):
            raise ValueError("bench weights do not match the program's "
                             "parameter layout")
        wa = NamedSharding(self.mesh, P("data"))
        # The rounds run under the mesh, as launch/train.py runs them; it
        # stays entered until release(), so that no call traces anew.
        self._ctx.enter_context(compat.use_mesh(self.mesh))
        self.state = jax.jit(make_state, out_shardings=shardings)(self._kp)
        self._batch = self._gen_batch(wa)
        self._next = self._batch(self.batch_key(0))
        self.jstep = steps_lib.compile_train_step(fn)

    def hlo_text(self):
        """The compiled step's HLO (a cache hit of the step's compile)."""
        if self.jstep is None:
            return None
        return self.jstep.lower(self.state, self._next,
                                self.step_key(self.i)).compile().as_text()

    def flops_per_round(self):
        return self.mod.flops_per_round(self.cfg, self.t)

    # -- the timed path --------------------------------------------------
    def round(self):
        """Dispatch one round (its batch made on the device first); returns
        something to wait on."""
        i = self.i
        self.i += 1
        if self.impl != "program":
            return self.stepper.step()[0]
        batch = self._next
        self.state, metrics = self.jstep(self.state, batch, self.step_key(i))
        return metrics

    def next_input(self):
        """Make the next round's batch (its own span in a trace)."""
        if self.impl == "program":
            self._next = self._batch(self.batch_key(self.i))

    def first_steps(self) -> dict:
        if self.impl != "program":
            return self._stepper_readings(self.stepper)
        copy = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: jnp.array(x, copy=True), t))
        p0 = copy(self.state["params"])
        losses, grad = [], None
        for k in range(self.steps):
            m = self.round()
            self.next_input()
            losses.append(m["loss"])
            if k == 0:
                grad = self._first_grad()
        change = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))(
                self.state["params"], p0)
        out = {"loss": [float(x) for x in losses], "grad": grad,
               "change": leaf_norms(change)}
        del p0, change
        return out

    def _first_grad(self):
        """The first aggregate as the optimizer got it, worked out from its
        state after one round."""
        if self.t["optimizer"] == "adamw":
            mu = self.state["opt"].mu
            return leaf_norms(jax.tree_util.tree_map(lambda m: m / (1 - B1), mu))
        raise ValueError(f"no first-gradient reading for "
                         f"{self.t['optimizer']!r}")

    def release(self):
        self.state = self.jstep = self._next = None
        self._ctx.close()

    # -- the reference ---------------------------------------------------
    def _reference(self, variant):
        dev0 = self.devices[0]
        key = ("inputs", json.dumps(self.cfg, sort_keys=True), self.w,
               self.t["per_worker_batch"], self.t["seq"])
        if key not in _COMPILED:
            _COMPILED[key] = (jax.jit(lambda k, cfg=self.cfg, mod=self.mod:
                                      mod.init_params(k, cfg)),
                              self._gen_batch())
        init, gen = _COMPILED[key]
        with jax.default_device(dev0):
            params0 = init(self._kp)
        return MeshReference(
            self.cell, self.mod, params0,
            lambda i: gen(jax.device_put(self.batch_key(i), dev0)),
            self.step_key, self.devices, variant=variant)

    def _stepper_readings(self, r) -> dict:
        p0 = r.params
        losses, first = [], None
        for k in range(self.steps):
            agg, loss = r.step()
            self.i += 1
            losses.append(loss)
            if k == 0:
                first = leaf_norms(agg)
        change = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            r.params, p0)
        return {"loss": losses, "grad": first, "change": leaf_norms(change)}

    def reference(self, variant: str = "f32") -> dict:
        with jax.default_matmul_precision("highest"):
            r = self._reference(variant)
            return self._stepper_readings(r)
