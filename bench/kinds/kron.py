"""Kron-Matmul cells: back-to-back ``KronOp`` calls from one caller, as a
solver or a training loop makes them, with calls queued ahead as far as
device memory holds their outputs.

Configuration keys: ``m``, ``ps``, ``qs``, ``dtype``, optionally ``mesh``
(``shape`` and ``axes``: X's rows over the first axis, columns over the
second) and with it ``n_slabs`` (``KronOp``'s).  Traffic keys: ``call``
(``forward`` or ``value_and_grad``: the gradient of ``<Y, ct>`` in X and
every factor, the cotangent ``ct`` drawn from the seed) and ``ahead_s``
(seconds of calls kept queued, ``harness.queue_depth``).

X, the factors and ``ct`` are made on the device from the seed in one
jitted call.  The answers of one call drawn from the seed and of the last
call are kept and compared, after the window, with the float32 reference
(``reference/kron.py``) run in blocks of rows on the first chip.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench.harness import per_chip_bytes, queue_depth, seed_key

WARM_CALLS = 3


class Driver:
    def __init__(self, cell, seed: int, devices, *, log=print):
        import jax.numpy as jnp

        c = cell.config
        self.cell, self.seed, self.devices, self.log = cell, int(seed), devices, log
        self.m, self.ps, self.qs = int(c["m"]), tuple(c["ps"]), tuple(c["qs"])
        self.dtype = jnp.dtype(c["dtype"])
        call = cell.traffic["call"]
        if call not in ("forward", "value_and_grad"):
            raise ValueError(f"unknown Kron call {call!r}")
        self.grad = call == "value_and_grad"
        self.kept: dict[str, object] = {}
        self.last = None
        self.mesh, self.data_sh, self.rep_sh = self._shardings()

    def _shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding
        from repro.runtime.sharding import make_mesh

        mesh_cfg = self.cell.config.get("mesh")
        if not mesh_cfg:
            one = SingleDeviceSharding(self.devices[0])
            return None, one, one
        shape = tuple(mesh_cfg["shape"])
        if math.prod(shape) != len(self.devices):
            raise ValueError(f"mesh {shape} needs {math.prod(shape)} chips, "
                             f"the cell has {len(self.devices)}")
        mesh = make_mesh(shape, tuple(mesh_cfg["axes"]), devices=self.devices)
        return mesh, NamedSharding(mesh, P(*mesh_cfg["axes"])), NamedSharding(mesh, P())

    # -- the program --------------------------------------------------------

    def _program(self):
        """The jitted timed function and the shapes of its arguments."""
        import jax
        import jax.numpy as jnp
        from repro.core.engine import KronOp

        kw = {}
        if self.mesh is not None:
            axes = self.cell.config["mesh"]["axes"]
            kw = dict(mesh=self.mesh, data_axis=axes[0], model_axis=axes[1],
                      n_slabs=self.cell.config["n_slabs"])
        op = KronOp(self.ps, self.qs, m=self.m, dtype_bytes=self.dtype.itemsize, **kw)
        self.log(f"[kron] {self.cell.name}: {op.describe()}")
        spec = lambda shape, sh: jax.ShapeDtypeStruct(shape, self.dtype, sharding=sh)  # noqa: E731
        x = spec((self.m, math.prod(self.ps)), self.data_sh)
        fs = tuple(spec((p, q), self.rep_sh) for p, q in zip(self.ps, self.qs))
        if not self.grad:
            return jax.jit(lambda x, fs: op(x, fs)), (x, fs)

        def loss(x, fs, ct):
            y = op(x, fs)
            return jnp.vdot(y.astype(jnp.float32), ct.astype(jnp.float32)), y

        ct = spec((self.m, math.prod(self.qs)), self.data_sh)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)), (x, fs, ct)

    def compile_abstract(self):
        """The timed program compiled from shapes alone (no device needed)."""
        fn, specs = self._program()
        return fn.lower(*specs).compile()

    def make_inputs(self):
        """X, the factors and (for a gradient cell) the cotangent from the
        seed, in one jitted call."""
        import jax
        import jax.numpy as jnp

        m, ps, qs, dt, grad = self.m, self.ps, self.qs, self.dtype, self.grad

        def make(key):
            ks = jax.random.split(key, len(ps) + 2)
            x = jax.random.normal(ks[0], (m, math.prod(ps)), jnp.float32).astype(dt)
            fs = tuple((jax.random.normal(k, (p, q), jnp.float32) / math.sqrt(p)).astype(dt)
                       for k, p, q in zip(ks[1:], ps, qs))
            if not grad:  # a forward cell holds no cotangent
                return x, fs, None
            ct = jax.random.normal(ks[-1], (m, math.prod(qs)), jnp.float32).astype(dt)
            return x, fs, ct

        outs = (self.data_sh, tuple(self.rep_sh for _ in ps), self.data_sh if grad else None)
        self.x, self.fs, self.ct = jax.block_until_ready(
            jax.jit(make, out_shardings=outs)(seed_key(self.seed)))

    def setup(self, seconds: float):
        import jax

        self.make_inputs()
        fn, _ = self._program()
        self.args = (self.x, self.fs, self.ct) if self.grad else (self.x, self.fs)
        self.fn = fn.lower(*self.args).compile()
        times, out = [], None
        for _ in range(WARM_CALLS):
            out = None  # one call's outputs alive at a time, as in the window
            t = time.perf_counter()
            out = jax.block_until_ready(self.fn(*self.args))
            times.append(time.perf_counter() - t)
        out_bytes = per_chip_bytes(out)
        del out
        # Each queued call holds its outputs; the sampled call's are kept too.
        self.ahead = queue_depth(self.devices, min(times),
                                 float(self.cell.traffic.get("ahead_s", 0)),
                                 out_bytes, reserve=out_bytes,
                                 temp_bytes=self.fn.memory_analysis().temp_size_in_bytes)
        expected = max(1, int(0.9 * seconds / max(min(times), 1e-6)))
        self.sample = int(np.random.default_rng(self.seed).integers(0, expected))

    # -- the timed call -----------------------------------------------------

    def call(self, i: int):
        """Dispatch call ``i``; its outputs, for the window to wait on."""
        self.last = None  # what the queue no longer holds is freed first
        out = self.fn(*self.args)
        if i == self.sample:
            self.kept["sample"] = out
        self.last = out
        return out

    def kernel_names(self):
        from repro.kernels.emit import KERNEL_NAMES

        return KERNEL_NAMES

    def work(self) -> dict:
        from bench.work import kron_call_work

        return kron_call_work(self.m, self.ps, self.qs, self.dtype.itemsize,
                              grad=self.grad, chips=len(self.devices))

    # -- the check ----------------------------------------------------------

    def _put(self):
        import jax

        if self.mesh is None:
            return lambda a: a
        return lambda a: jax.device_put(a, self.devices[0])

    def _judge(self, answers) -> dict:
        from bench.reference import kron as ref

        errs = ref.compare(self.x, self.fs, answers, ct=self.ct if self.grad else None,
                           put=self._put())
        return {k: {"value": v, "limit": float(self.cell.limits[k])}
                for k, v in sorted(errs.items())}

    def check(self) -> dict:
        """Every kept answer against the reference; the program is freed first."""
        outs = list(self.kept.values()) + [self.last]
        self.kept.clear()
        self.last = self.fn = self.args = None
        if self.grad:
            answers = [(y, dx, dfs) for (_, y), (dx, dfs) in outs]
        else:
            answers = [(y, None, None) for y in outs]
        return self._judge(answers)

    def control(self, mode: str = "high") -> dict:
        """The control's readings: the reference at ``mode`` in the program's
        place, judged as the program's answers are."""
        from bench.reference import kron as ref

        # A mesh cell's answers fill the chips together: the control keeps
        # its blocks on the host, beside the one chip the reference runs on.
        keep = np.asarray if self.mesh is not None else (lambda a: a)
        y, dx, dfs = ref.control_outputs(self.x, self.fs, self.ct if self.grad else None,
                                         mode=mode, put=self._put(), keep=keep)
        return self._judge([(y, dx, dfs)])
