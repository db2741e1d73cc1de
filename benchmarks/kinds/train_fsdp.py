"""Cells of kind ``train_fsdp``: ``kinds/train.py``'s run, unchanged, on a
mesh of chips (the cell's ``mesh``, which the program's trainer is built
with), and its reference sharded over the same chips.

Nothing of that run is copied: this module loads ``kinds/train.py`` once
more under a name of its own, and ``reference/dalle.py`` once more, and
gives the first the second. The reference's ``first_steps`` is unchanged;
what differs is where its arrays live. Its programs are jitted over a mesh
of the cell's chips with the cell's axes: every parameter, every gradient and
every optimizer moment sharded along its largest axis that the ``fsdp`` axis
divides (a layer's leaf along one of its own axes, never the axis of layers
that the reference scans), as the program shards its own state (ZeRO-like,
``parallel/partition.py``), and the batch's rows over the mesh. Adam's
float32 masters and two moments of the 1.4B flagship are about 23 GB with
the gradients, which no one chip holds.
"""

from __future__ import annotations

import functools

from benchmarks import harness
from benchmarks.kinds import train
from benchmarks.reference import dalle

_run = harness.load_module(train.__file__, __name__ + "._train")
_ref = harness.load_module(dalle.__file__, __name__ + "._dalle")
_run.ref = _ref
_plain_optimizers = dict(_ref.OPTIMIZERS)
_on = {}        # the mesh the reference runs over: the cell's, set per run


def _mesh(cell: dict):
    import jax
    import numpy as np
    axes = tuple(cell["mesh"])
    shape = tuple(int(cell["mesh"][a]) for a in axes)
    devices = np.asarray(jax.devices()[:cell["chips"]]).reshape(shape)
    return jax.sharding.Mesh(devices, axes)


def _sharded(tree):
    """Each leaf's ``NamedSharding`` on the run's mesh: along its largest
    axis that ``fsdp`` divides, the axis of layers (a leaf under ``layers``)
    left whole; replicated where no axis divides."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _on["mesh"]
    n = mesh.shape["fsdp"]

    def one(path, leaf):
        first = 1 if any(getattr(k, "key", None) == "layers"
                         for k in path) else 0
        axes = [i for i in range(first, len(leaf.shape))
                if leaf.shape[i] % n == 0]
        if not axes:
            return NamedSharding(mesh, P())
        at = max(axes, key=lambda i: leaf.shape[i])
        return NamedSharding(mesh, P(*(["fsdp" if i == at else None
                                        for i in range(len(leaf.shape))])))
    return jax.tree_util.tree_map_with_path(one, tree)


def _init_on_mesh(init):
    """An optimizer's ``init``, jitted with its moments sharded."""
    import jax

    def sharded_init(params):
        moments = _sharded(jax.eval_shape(init, params))
        return jax.jit(init, out_shardings=moments)(params)
    return sharded_init


@functools.lru_cache(maxsize=None)
def _programs(s, optimizer: str, lr: float, clip: float, precision: str):
    """``reference/dalle.py``'s three programs, jitted over the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _on["mesh"]
    shapes = jax.eval_shape(lambda: _ref.init_params(
        s, jax.random.PRNGKey(0)))
    params = _sharded(shapes)
    moments = _sharded(jax.eval_shape(_plain_optimizers[optimizer][0],
                                      shapes))
    rows = NamedSharding(mesh, P(tuple(mesh.axis_names)))

    def change(p, key):
        start = _ref.init_params(s, key)
        delta = jax.tree.map(lambda a, b: a - b, p, start)
        return _ref.per_leaf(delta, lambda d: jax.numpy.sqrt(
            jax.numpy.sum(d * d)))

    return (jax.jit(lambda key: _ref.init_params(s, key),
                    out_shardings=params),
            jax.jit(_ref.make_step(s, optimizer, lr, clip, precision),
                    in_shardings=(params, moments, rows, rows),
                    out_shardings=(params, moments, None),
                    donate_argnums=(0, 1)),
            jax.jit(change, in_shardings=(params, None)))


_ref._programs = _programs
_ref.OPTIMIZERS = {name: (_init_on_mesh(init), update)
                   for name, (init, update) in _plain_optimizers.items()}
_reference_numbers = _run.reference_numbers


def reference_numbers(cell: dict, cfg: dict, seed: int, **kw) -> dict:
    """``kinds/train.py``'s, over the cell's mesh."""
    _on["mesh"] = _mesh(cell)
    return _reference_numbers(cell, cfg, seed, **kw)


_run.reference_numbers = reference_numbers
calibrate = _run.calibrate


def run_cell(cell: dict, cfg: dict, **kw) -> dict:
    """One run of a train_fsdp cell. Returns the keyword arguments of
    ``harness.finish``."""
    if not cell.get("mesh", {}).get("fsdp"):
        raise SystemExit(f"{cell['name']}: a train_fsdp cell names its mesh, "
                         f"with an fsdp axis")
    return _run.run_cell(cell, cfg, **kw)
