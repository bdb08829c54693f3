"""How the train state is stored: the layouts of params, optimizer
moments and the GNS carry on the mesh, and their dp-independent
canonical form on disk.

One decision, four answers, each a small class with the same surface:

    replicated     every data-parallel device holds the whole state
                   (``param_sharding_fn`` and the stage / expert axes
                   lay out individual leaves).
    zero1          moments as flat ``[dp, shard]`` rows over the data
                   axis, params replicated (ZeRO stage 1).
    zero3-lite     zero1, and the params stored as rows too; the step
                   assembles the whole tree once at its start.
    zero3-blocks   per-layer FSDP: params, moments and the carry as
                   per-block rows; the model gathers one block at a
                   time (``adaptdl_tpu.parallel.zero3``).

A layout owns its validity rules and sizes, the fresh state and its
PartitionSpec tree, what the train step does differently per layout
(what the loss is differentiated against, how the gradient is reduced,
the GNS squared norm and count, the preconditioner, the optimizer
update), and ``to_canonical`` / ``from_canonical``. The canonical
transforms are array code written once: the caller passes ``run`` to
say where they execute (:func:`on_host` for the pickle checkpoint,
:func:`on_mesh` for the orbax one), the layout says what they compute.
``ElasticTrainer`` resolves its ``zero1`` / ``zero3`` /
``zero3_blocks`` arguments to one layout with :func:`resolve` and
keeps the single step skeleton; a new way of storing the state is a
new class here.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from adaptdl_tpu import gns
from adaptdl_tpu.parallel import zero3 as z3
from adaptdl_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    PARAM_SHARDED_AXES,
    SEQ_AXIS,
)


def materialize(x, sharding) -> jax.Array:
    """Place a host/device value onto a (possibly multi-process) mesh.

    ``jax.device_put`` only accepts shardings whose devices are all
    addressable from this process; on a multi-host mesh each process
    must instead supply its local shards via
    ``jax.make_array_from_callback``. PRNG key arrays round-trip
    through their raw key data (callbacks produce plain arrays).
    """
    if isinstance(x, jax.Array) and jax.dtypes.issubdtype(
        x.dtype, jax.dtypes.prng_key
    ):
        data = jax.random.key_data(x)
        placed = materialize(np.asarray(jax.device_get(data)), sharding)
        return jax.random.wrap_key_data(placed)
    if sharding.is_fully_addressable:
        if isinstance(x, jax.Array):
            # Copy: device_put aliases buffers whose sharding already
            # matches, and the donated train step would then delete
            # the caller's array out from under them.
            x = jnp.array(x, copy=True)
        return jax.device_put(x, sharding)
    host = np.asarray(jax.device_get(x))
    return jax.make_array_from_callback(
        host.shape, sharding, lambda idx: host[idx]
    )


def abstract(tree):
    """The tree's shapes and dtypes."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x)),
        tree,
    )


def device_bytes(tree) -> int:
    """Bytes ONE device holds of a tree of placed arrays (its first
    addressable shard of every leaf)."""
    return sum(
        int(leaf.addressable_shards[0].data.nbytes)
        for leaf in jax.tree.leaves(tree)
        if isinstance(leaf, jax.Array)
        and not jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.prng_key)
    )


def restrict_specs(specs, manual_axes: set):
    """Keep only the shard_map's MANUAL axes in a spec tree:
    pipeline-stage components stay (they are sharded inside the
    step), model-axis components drop (GSPMD auto handles them)."""

    def restrict(spec):
        kept = []
        for part in spec or ():
            if part is None:
                kept.append(None)
                continue
            # A dim may be sharded over SEVERAL axes at once
            # (tuple entry, e.g. ("stage", "model")): filter
            # inside it rather than dropping the whole entry.
            axes = (part,) if isinstance(part, str) else tuple(part)
            axes = tuple(a for a in axes if a in manual_axes)
            if not axes:
                kept.append(None)
            elif len(axes) == 1:
                kept.append(axes[0])
            else:
                kept.append(axes)
        while kept and kept[-1] is None:
            kept.pop()
        return P(*kept)

    return jax.tree.map(
        restrict, specs, is_leaf=lambda x: isinstance(x, P)
    )


# ---- where a canonical transform runs ------------------------------------
#
# ``run(fn, specs)`` returns ``fn`` bound to a place of execution;
# ``specs`` is the PartitionSpec (tree, or one spec as a prefix) of its
# result in the run layout.


def on_host(fn: Callable, specs=None) -> Callable:
    """Host numpy in, host numpy out (``TrainerCheckpoint``)."""
    return lambda *args: jax.tree.map(np.asarray, fn(*args))


def on_mesh(mesh) -> Callable:
    """Device collectives under ``jax.jit(..., out_shardings=...)``:
    no host gather, so the path works multi-host where the host-numpy
    form cannot (``ShardedTrainerCheckpoint``)."""

    def run(fn, specs):
        return jax.jit(
            fn,
            out_shardings=jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                specs,
                is_leaf=lambda x: isinstance(x, P),
            ),
        )

    return run


def inline(fn: Callable, specs=None) -> Callable:
    """Inside a caller's own trace (``jax.eval_shape`` of a transform
    gives the abstract canonical form)."""
    return fn


def _xp(x):
    return np if isinstance(x, np.ndarray) else jnp


def _rows_to_flat(rows, n: int):
    """``[dp, shard]`` rows -> the ``[n]`` vector, pad trimmed."""
    return rows.reshape(-1)[:n]


def _flat_to_rows(flat, dp: int, shard: int):
    """``[n]`` vector -> ``[dp, shard]`` rows, padded for THIS dp."""
    xp = _xp(flat)
    pad = dp * shard - flat.shape[0]
    if pad:
        flat = xp.concatenate([flat, xp.zeros((pad,), flat.dtype)])
    return flat.reshape(dp, shard)


def _adam_nu(opt_state):
    """Adam's second-moment tree inside an optax state."""

    def find(node):
        if isinstance(node, optax.ScaleByAdamState):
            return node.nu
        if isinstance(node, tuple):
            for child in node:
                found = find(child)
                if found is not None:
                    return found
        return None

    nu = find(opt_state)
    if nu is None:
        raise ValueError(
            "precondition='adam' but optimizer state has no "
            "ScaleByAdamState"
        )
    return nu


class Layout:
    """The replicated layout, and the surface every layout has."""

    name = "replicated"
    # Whether a checkpoint's leaves are the run layout's, position for
    # position (the handoff's default shard plan reads row spans off
    # the run spec tree).
    canonical_is_stored = True
    # Whether :meth:`reduce` all-reduces the gradient over the data
    # axis: what the backward of a step's last micro-batch can run
    # beside (``ElasticTrainer._reduce_has_tail``).
    reduces_gradient = True

    def __init__(
        self,
        *,
        mesh,
        params: Any,
        optimizer: optax.GradientTransformation,
        param_sharding_fn: Callable | None,
        group_ids: tuple,
        num_groups: int,
        precondition: str | None,
    ):
        self.mesh = mesh
        # The parameter TREE's shapes and dtypes, never its values: a
        # layout keeps no copy of the parameters beside the state
        # (init() is handed them).
        self.template = abstract(params)
        self.optimizer = optimizer
        self.param_sharding_fn = param_sharding_fn
        self.group_ids = group_ids
        self.num_groups = num_groups
        self.precondition = precondition
        self.dp = mesh.shape[DATA_AXIS]
        self.seq_shards = mesh.shape.get(SEQ_AXIS, 1)
        self.sharded_axes = tuple(
            axis
            for axis in PARAM_SHARDED_AXES
            if mesh.shape.get(axis, 1) > 1
        )

    def _put(self, x, spec):
        return materialize(x, NamedSharding(self.mesh, spec))

    def _zeros(self):
        """Zeros of the template's shapes: what :meth:`build` under
        ``jax.eval_shape`` and a re-primed carry are made from."""
        return jax.tree.map(
            lambda t: jnp.zeros(t.shape, t.dtype), self.template
        )

    # ---- the fresh state and its specs --------------------------------

    def template_specs(self):
        """PartitionSpec tree of the parameter TREE."""
        if self.param_sharding_fn is None:
            return jax.tree.map(lambda _: P(), self.template)
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: self.param_sharding_fn(path, leaf),
            self.template,
        )

    def param_specs(self):
        """PartitionSpec tree of ``state.params`` as stored."""
        return self.template_specs()

    def state_specs(self, state):
        """PartitionSpec tree for a full TrainState.

        Params take ``param_sharding_fn`` specs; derived trees that
        mirror the params — optimizer moments, the GNS prev-grad — take
        the *same* specs, identified by path suffix + shape (an optax
        ``mu`` leaf's path ends with the corresponding param's path).
        Everything else (counts, EMA scalars, rng, progress) is
        replicated.
        """
        if self.param_sharding_fn is None:
            return jax.tree.map(lambda _: P(), state)
        param_leaves = jax.tree_util.tree_flatten_with_path(state.params)[0]
        spec_leaves = jax.tree.leaves(
            self.template_specs(), is_leaf=lambda x: isinstance(x, P)
        )
        matchers = [
            (tuple(path), np.shape(leaf), spec)
            for (path, leaf), spec in zip(param_leaves, spec_leaves)
        ]

        def assign(path, leaf):
            path = tuple(path)
            for ppath, shape, spec in matchers:
                if (
                    len(path) >= len(ppath)
                    and path[-len(ppath):] == ppath
                    and np.shape(leaf) == shape
                ):
                    return spec
            return P()

        return jax.tree_util.tree_map_with_path(assign, state)

    def build(self, params=None):
        """The fresh ``(params, opt_state, gns)`` in run layout, as a
        traceable function of the parameters (zeros of the template
        when not given): ``jax.eval_shape`` of it is the state's
        skeleton before any state exists."""
        values = self._zeros() if params is None else params
        return (
            values,
            self.optimizer.init(values),
            self._fresh_gns(values),
        )

    def _fresh_gns(self, params):
        return gns.init(params, self.num_groups)

    def init(self, params):
        """The fresh ``(params, opt_state, gns)`` on the mesh from the
        initial ``params``: data-parallel leaves replicated,
        tensor-parallel params laid out per ``param_sharding_fn``."""
        specs = self.template_specs()
        params = jax.tree.map(self._put, params, specs)
        return (
            params,
            self._init_moments(params),
            self._place_gns(self._fresh_gns(params), specs),
        )

    def _init_moments(self, params):
        # Optimizer moments follow the params' layout: eager
        # zeros_like on a sharded array preserves its sharding. Leaves
        # the optimizer creates itself (Adam's step count) land on the
        # default device only: replicate them over the mesh like every
        # other leaf, so that a fresh state has exactly the placement
        # a restored one gets — the AOT executable cache keys on it,
        # and incarnation 0's entry must serve incarnation 1.
        return jax.tree.map(
            lambda x: x
            if isinstance(x.sharding, NamedSharding)
            else self._put(x, P()),
            self.optimizer.init(params),
        )

    def _place_gns(self, gns_state, carry_specs):
        return gns_state._replace(
            prev_grad=jax.tree.map(
                self._put, gns_state.prev_grad, carry_specs
            ),
            sqr_biased=self._put(gns_state.sqr_biased, P()),
            sqr_unbias=self._put(gns_state.sqr_unbias, P()),
            var_biased=self._put(gns_state.var_biased, P()),
            var_unbias=self._put(gns_state.var_unbias, P()),
            ema_is_biased=self._put(gns_state.ema_is_biased, P()),
            prev_grad_valid=self._put(gns_state.prev_grad_valid, P()),
        )

    # ---- inside the step's shard_map ----------------------------------

    @property
    def varying_axes(self):
        """The axes the model's values vary over."""
        return (
            (DATA_AXIS, SEQ_AXIS) if self.seq_shards > 1 else DATA_AXIS
        )

    def assemble(self, stored):
        """``state.params`` (this device's part) -> the form the
        gradient is taken in and the optimizer updates."""
        return stored

    def model_params(self, stored):
        """What a ``loss_fn`` / ``metric_fn`` receives."""
        return self.assemble(stored)

    def differentiable(self, params):
        """What the loss is differentiated against. A per-replica
        *varying* view of the params: under shard_map's vma system,
        grads of replicated params are auto-psum'ed across the mesh,
        which would hand every replica the summed gradient and erase
        the per-replica noise signal the GNS needs. Varying params
        keep gradients local; :meth:`reduce` takes the cross-replica
        mean explicitly."""
        return jax.lax.pcast(params, self.varying_axes, to="varying")

    def differentiated(self, loss_fn):
        """``loss_fn`` as a function of what :meth:`differentiable`
        returns (and then batch, rng and aux): what the step and the
        calibration program hand to ``jax.value_and_grad``."""
        return loss_fn

    def whole_sample(self, loss, grad):
        """One micro-batch's loss and raw gradient on this device ->
        the loss and gradient of its whole samples."""
        if self.seq_shards > 1:
            # A sequence-sharded group is one logical replica:
            # average its shard-gradients *before* the GNS
            # squared norm so the noise statistics see whole-
            # sample gradients.
            grad = jax.lax.pmean(grad, SEQ_AXIS)
            loss = jax.lax.pmean(loss, SEQ_AXIS)
        return loss, grad

    def precond(self, opt_state):
        """The GNS preconditioner, or None."""
        if self.precondition != "adam":
            return None
        return jax.tree.map(
            lambda v: jnp.sqrt(jnp.maximum(v, 0.0)) + 1e-8,
            _adam_nu(opt_state),
        )

    def micro_precond(self, precond):
        """The preconditioner as :meth:`normsqr` takes it for a
        micro-batch gradient. It multiplies gradients *after* their
        seq-axis pmean, so it is data-varying only."""
        if precond is None:
            return None
        return jax.lax.pcast(precond, DATA_AXIS, to="varying")

    @functools.cached_property
    def _leaf_psum_axes(self):
        # Per-leaf psum axes for gradient-norm statistics: a leaf
        # sharded over stage/expert contributes a psum'd term; a
        # replicated leaf's gradient is already complete on every
        # device (vma auto-psums its cotangents over those axes) and
        # must not be double-counted.
        manual_specs = restrict_specs(
            self.template_specs(), set(self.sharded_axes)
        )
        return tuple(
            tuple(
                axis
                for part in (spec or ())
                if part is not None
                for axis in (
                    (part,) if isinstance(part, str) else tuple(part)
                )
                if axis in self.sharded_axes
            )
            for spec in jax.tree.leaves(
                manual_specs, is_leaf=lambda x: isinstance(x, P)
            )
        )

    def normsqr(self, tree, pre=None):
        """Per-group squared norm of a gradient-shaped tree."""
        return gns.sharded_group_normsqr(
            tree, self.group_ids, self.num_groups,
            self._leaf_psum_axes, pre,
        )

    def gns_count(self, num_micro: int) -> int:
        """How many gradient samples one step's ``normsqr`` mean
        covers."""
        return self.dp * num_micro

    def accumulators(self, params):
        """Zeros for the scan's (gradient, squared norm, loss) sums.
        The gradient's derive from the params so they inherit their
        varying-axis types (stage-sharded leaves are stage-varying; a
        literal zeros array would be typed unvarying and fail the scan
        carry check), then add the data axis. The loss carry stays
        stage-UNvarying (a pipelined loss_fn psums over the stage
        axis); the squared norm is already psum'd over the sharded
        axes inside :meth:`normsqr`, so it varies over data only."""
        zeros = jax.tree.map(
            lambda p: (p * 0.0).astype(jnp.float32), params
        )
        return (
            jax.lax.pcast(zeros, DATA_AXIS, to="varying"),
            jax.lax.pcast(
                jnp.zeros((self.num_groups,)), DATA_AXIS, to="varying"
            ),
            jax.lax.pcast(jnp.zeros(()), DATA_AXIS, to="varying"),
        )

    def reduce(self, grad_sum, lsqr_sum, loss_sum, num_micro: int):
        """The gradient all-reduce: a ``pmean`` over the data axis (ICI
        or DCN), one all-reduce a leaf as lowered — the compiler's
        combiner merges them into a few, except under
        ``trainer.REDUCE_OVERLAP_OPTIONS`` — with the GNS scalars
        riding alongside. Pipeline stages do NOT
        average gradients — each stage owns its parameter shard — but
        the gradient-norm statistics sum across the shards."""
        grads_local = jax.tree.map(lambda g: g / num_micro, grad_sum)
        return (
            jax.lax.pmean(grads_local, DATA_AXIS),
            jax.lax.pmean(lsqr_sum / num_micro, DATA_AXIS),
            jax.lax.pmean(loss_sum / num_micro, DATA_AXIS),
        )

    def apply(self, grads, opt_state, params, stored, group_factors):
        """The optimizer update -> ``(new state.params, new
        opt_state)``. Each leaf's update scales by ITS group's factor
        (the reference multiplies scale_lr's vector into each
        optimizer param group's lr, scaling_rules.py:78-83)."""
        updates, new_opt_state = self.optimizer.update(
            grads, opt_state, params
        )
        flat_updates, treedef = jax.tree_util.tree_flatten(updates)
        flat_updates = [
            (u.astype(jnp.float32) * group_factors[gid]).astype(u.dtype)
            for u, gid in zip(flat_updates, self.group_ids)
        ]
        updates = jax.tree_util.tree_unflatten(treedef, flat_updates)
        return optax.apply_updates(params, updates), new_opt_state

    # ---- outside the step ---------------------------------------------

    def full_params(self, stored):
        """The parameter TREE of ``state.params``, replicated."""
        return stored

    def to_canonical(self, state, run: Callable = on_host):
        """Run layout -> the dp-independent layout a checkpoint
        stores. ``state`` is anything with ``params`` / ``opt_state`` /
        ``gns`` fields and ``_replace``."""
        return state._replace(
            params=self.params_to_canonical(state.params, run),
            opt_state=self.moments_to_canonical(state.opt_state, run),
            gns=self.carry_to_canonical(state.gns, run),
        )

    def from_canonical(self, state, run: Callable = on_host):
        """A checkpoint's layout -> THIS mesh's run layout."""
        return state._replace(
            params=self.params_from_canonical(state.params, run),
            opt_state=self.moments_from_canonical(state.opt_state, run),
            gns=self.carry_from_canonical(state.gns, run),
        )

    def params_to_canonical(self, stored, run=on_host):
        return stored

    def params_from_canonical(self, tree, run=on_host):
        return tree

    def moments_to_canonical(self, opt_state, run=on_host):
        return opt_state

    def moments_from_canonical(self, opt_state, run=on_host):
        return opt_state

    def carry_to_canonical(self, gns_state, run=on_host):
        return gns_state

    def carry_from_canonical(self, gns_state, run=on_host):
        return gns_state

    def legacy_carries(self) -> list:
        """Abstract ``prev_grad`` trees that older checkpoints hold
        where the canonical form is expected today, and that
        :meth:`carry_from_canonical` still reads."""
        return []


class _DataSharded(Layout):
    """What the layouts that shard over the data axis share: the
    moments mirror the stored params position for position, and are a
    flat ``[n]`` vector in ``ravel_pytree(tree)`` order on disk, so a
    rescale changes dp freely and a checkpoint may cross between
    them."""

    canonical_is_stored = False
    mirror_specs: Any = P(DATA_AXIS)

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        if (
            self.sharded_axes
            or MODEL_AXIS in self.mesh.shape
            or self.param_sharding_fn is not None
        ):
            raise ValueError(self._composes)
        flat, self.unravel = ravel_pytree(self._zeros())
        self.n = int(flat.size)

    @functools.cached_property
    def _tree_on_mesh(self):
        # Assemble ON DEVICE: rows are sharded over the data axis and
        # not fully addressable on multi-host jobs, so a host-side
        # np.asarray would crash exactly where sharded params matter.
        # A jit with replicated out_shardings makes XLA all-gather the
        # rows and unravel them into the canonical tree.
        return on_mesh(self.mesh)(self._rows_to_tree, P())

    def mirrors(self, opt_state) -> list:
        """The params-shaped subtrees of an optimizer state."""
        found = []
        self._map_mirrors(opt_state, found.append)
        return found

    def _map_mirrors(self, opt_state, fn, other=lambda x: x):
        """``fn`` over every subtree of the optimizer state that stands
        where the optimizer keeps a copy of the params' structure —
        known from how the optimizer builds its state, whatever layout
        those subtrees are in."""
        return optax.tree_map_params(
            self.optimizer, fn, opt_state,
            transform_non_params=other, is_leaf=lambda _: True,
        )

    def state_specs(self, state):
        return jax.tree.map(lambda _: P(), state)._replace(
            params=self.param_specs(),
            opt_state=self._map_mirrors(
                state.opt_state,
                lambda _: self.mirror_specs,
                lambda _: P(),
            ),
            gns=jax.tree.map(lambda _: P(), state.gns)._replace(
                prev_grad=self.carry_specs()
            ),
        )

    def moments_to_canonical(self, opt_state, run=on_host):
        return self._map_mirrors(
            opt_state, run(self._mirror_to_flat, P())
        )

    def moments_from_canonical(self, opt_state, run=on_host):
        return self._map_mirrors(
            opt_state, run(self._mirror_from_canonical, self.mirror_specs)
        )

    def _is_flat(self, node) -> bool:
        return getattr(node, "shape", None) == (self.n,) and np.shape(
            self.template
        ) != (self.n,)


class Zero1(_DataSharded):
    """ZeRO-1 optimizer-state sharding: the flattened parameter vector
    is partitioned across the data axis; each replica holds and updates
    1/dp of the optimizer moments (8 bytes/param under Adam drop to
    8/dp) and the updated shards are reassembled with one scatter+psum.
    The memory/comm trade: one extra parameter-sized all-reduce per
    step buys a dp-factor cut in optimizer-state HBM — worthwhile
    exactly when moments are a real fraction of HBM (large models),
    where steps are compute-dominated and the collective rides ICI
    under the compute. (ZeRO stage 1, Rajbhandari et al.;
    implementation original, built on the flat-vector psum pattern
    rather than torch's per-bucket broadcast.)

    The optimizer is initialized over the padded flat parameter vector
    reshaped ``[dp, shard]`` so its moment leaves shard ``P("data")``
    (dim 0) and each replica owns one row. Works for elementwise
    transforms (the Adam/SGD families); norm-based transforms
    (clip_by_global_norm) would see shard-local norms and are
    unsupported."""

    name = "zero1"
    _composes = (
        "zero1 shards optimizer state over the data axis "
        "and composes with data/seq parallelism only; "
        "stage/expert/model axes manage their own "
        "parameter and optimizer layouts"
    )

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self.pad = (-self.n) % self.dp
        self.shard = (self.n + self.pad) // self.dp
        # Flat group-id table for per-position LR factors — only
        # when groups actually differ: it costs 4 bytes/param of
        # replicated HBM (the slice start is rank-dynamic, so XLA
        # can't fold it), which would claw back half the moment
        # saving in the common single-group case.
        self._flat_gids = None
        if self.num_groups > 1:
            self._flat_gids = np.concatenate(
                [
                    np.full(int(np.prod(np.shape(leaf))), gid, np.int32)
                    for leaf, gid in zip(
                        jax.tree.leaves(self.template), self.group_ids
                    )
                ]
                + [np.zeros(self.pad, np.int32)]
            )

    # ---- the fresh state and its specs --------------------------------

    def tree_to_rows(self, params):
        """Param tree -> padded flat ``[dp, shard]`` rows (traceable)."""
        flat, _ = ravel_pytree(params)
        if self.pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((self.pad,), flat.dtype)]
            )
        return flat.reshape(self.dp, self.shard)

    def _init_rows_moments(self, params):
        return self.optimizer.init(self.tree_to_rows(params))

    def _empty_carry(self):
        """At dp > 1 the GNS differenced-estimator carry (prev_grad, a
        full f32 param-sized tree) backs ONLY the dp==1 single-sample
        estimator — gns.update's count>1 branch never reads it, so
        persisting it replicated would silently claw back the memory
        the zero family sheds. One-element placeholder leaves instead
        ((1,), not (0,): orbax refuses zero-size arrays); on disk
        always, so the canonical form is dp-independent and the dp==1
        reader re-primes."""
        return jax.tree.map(
            lambda _: jnp.zeros((1,), jnp.float32), self.template
        )

    def _full_carry(self):
        return jax.tree.map(
            lambda p: jnp.zeros(np.shape(p), jnp.float32), self.template
        )

    def carry_specs(self):
        return jax.tree.map(lambda _: P(), self.template)

    def _fresh_gns(self, params):
        gns_state = gns.init(params, self.num_groups)
        if self.dp > 1:
            gns_state = gns_state._replace(prev_grad=self._empty_carry())
        return gns_state

    def build(self, params=None):
        values = self._zeros() if params is None else params
        return (
            self._store(values),
            self._init_rows_moments(values),
            self._fresh_gns(values),
        )

    def _store(self, params):
        return params

    def init(self, params):
        specs = self.template_specs()
        params = jax.tree.map(self._put, params, specs)
        # Born sharded: jit with out_shardings so the moment rows
        # never exist replicated — an eager init would transiently
        # hold params + flat copy + both replicated moments per
        # device, an OOM risk at exactly the scale zero1 targets.
        abstract = jax.eval_shape(self._init_rows_moments, params)
        opt_state = on_mesh(self.mesh)(
            self._init_rows_moments,
            self._map_mirrors(
                abstract, lambda _: self.mirror_specs, lambda _: P()
            ),
        )(params)
        gns_state = self._place_gns(
            self._fresh_gns(params), self.carry_specs()
        )
        return self._store_on_mesh(params), opt_state, gns_state

    def _store_on_mesh(self, params):
        return params

    # ---- inside the step's shard_map ----------------------------------

    def gather(self, row_local):
        """This replica's ``[1, shard]`` row -> the full ``[n]`` flat
        vector. Scatter + psum over the data axis (psum output is typed
        invariant under the vma system, which a tiled all_gather is
        not)."""
        full = jnp.zeros((self.dp * self.shard,), row_local.dtype)
        full = jax.lax.pcast(full, DATA_AXIS, to="varying")
        rank = jax.lax.axis_index(DATA_AXIS)
        full = jax.lax.dynamic_update_slice(
            full, row_local[0], (rank * self.shard,)
        )
        return jax.lax.psum(full, DATA_AXIS)[: self.n]

    def precond(self, opt_state):
        # Each replica holds one [1, shard] row of Adam's nu;
        # reassemble the param-shaped tree with the same scatter+psum
        # the parameter update uses, then take sqrt.
        if self.precondition != "adam":
            return None
        nu_tree = self.unravel(self.gather(_adam_nu(opt_state)))
        return jax.tree.map(
            lambda v: jnp.sqrt(
                jnp.maximum(v.astype(jnp.float32), 0.0)
            )
            + 1e-8,
            nu_tree,
        )

    def _local_row(self, params, stored, start):
        flat_p, unravel = ravel_pytree(params)
        if self.pad:
            flat_p = jnp.concatenate(
                [flat_p, jnp.zeros((self.pad,), flat_p.dtype)]
            )
        row = jax.lax.dynamic_slice(flat_p, (start,), (self.shard,))
        return row[None], unravel

    def _store_row(self, new_row, unravel):
        # The full parameter vector is reassembled with scatter + psum
        # (typed invariant over the data axis, which a tiled
        # all_gather is not under the vma system).
        return unravel(self.gather(new_row))

    def apply(self, grads, opt_state, params, stored, group_factors):
        """The sharded optimizer step: slice this replica's row of the
        flat gradient vector, update it against the local [1, shard]
        moment row, and apply the per-position group LR factor."""
        shard = self.shard
        flat_g, _ = ravel_pytree(grads)
        if self.pad:
            flat_g = jnp.concatenate(
                [flat_g, jnp.zeros((self.pad,), flat_g.dtype)]
            )
        start = jax.lax.axis_index(DATA_AXIS) * shard
        g_sh = jax.lax.dynamic_slice(flat_g, (start,), (shard,))[None]
        p_sh, unravel = self._local_row(params, stored, start)
        updates_sh, new_opt = self.optimizer.update(g_sh, opt_state, p_sh)
        if self._flat_gids is None:
            factor_sh = group_factors[0]
        else:
            gid_sh = jax.lax.dynamic_slice(
                jnp.asarray(self._flat_gids), (start,), (shard,)
            )
            factor_sh = group_factors[gid_sh][None]
        updates_sh = (
            updates_sh.astype(jnp.float32) * factor_sh
        ).astype(updates_sh.dtype)
        new_p_sh = optax.apply_updates(p_sh, updates_sh)
        return self._store_row(new_p_sh, unravel), new_opt

    # ---- outside the step ---------------------------------------------

    def _mirror_to_flat(self, rows):
        return _rows_to_flat(rows, self.n)

    def _mirror_from_canonical(self, flat):
        return _flat_to_rows(flat, self.dp, self.shard)

    def carry_to_canonical(self, gns_state, run=on_host):
        if self.dp > 1:
            return gns_state  # the run layout is the placeholder
        return gns_state._replace(
            prev_grad=run(self._empty_carry, P())()
        )

    def carry_from_canonical(self, gns_state, run=on_host):
        prev = gns_state.prev_grad
        # Readable at dp == 1 only when it came in whole (a checkpoint
        # from before the placeholder): a placeholder, or a
        # zero3-blocks checkpoint's flat carry, has no zero1 reader.
        whole = not self._is_flat(prev) and not any(
            np.shape(leaf) == (1,) and np.shape(p) != (1,)
            for leaf, p in zip(
                jax.tree.leaves(prev), jax.tree.leaves(self.template)
            )
        )
        if self.dp == 1 and whole:
            return gns_state
        fresh, invalid = run(
            lambda: (
                self._empty_carry() if self.dp > 1 else self._full_carry(),
                jnp.zeros((), bool),
            ),
            P(),
        )()
        if self.dp > 1 and not self._is_flat(prev):
            # The carry is never read at dp > 1: placeholder layout,
            # whatever came in.
            return gns_state._replace(prev_grad=fresh)
        # The differenced estimator re-primes on its next step.
        return gns_state._replace(
            prev_grad=fresh, prev_grad_valid=invalid
        )

    def legacy_carries(self) -> list:
        return [jax.eval_shape(self._full_carry)]


class Zero3Lite(Zero1):
    """ZeRO-3-lite: zero1, and the PARAMETERS stored as flat
    ``[dp, shard]`` rows over the data axis too. The step assembles
    the full tree on the fly (scatter+psum, the FSDP all-gather) and
    the optimizer updates only this replica's row — which also makes
    the update path CHEAPER than zero1's (no parameter reassembly
    collective after the update; assembly happens once at step start).
    Storage per device: params n/dp + moments 2n/dp, vs n + 2n
    replicated — the transient full tree lives only inside the step.
    Params checkpoint in canonical TREE form (dp-independent; the same
    layout a dense trainer writes)."""

    name = "zero3-lite"

    def param_specs(self):
        return P(DATA_AXIS)

    def _store(self, params):
        return self.tree_to_rows(params)

    def _store_on_mesh(self, params):
        # Params born sharded too: each device ends with only its
        # [1, shard] row (the replicated tree was needed to seed the
        # optimizer/GNS mirrors and is dropped here).
        return on_mesh(self.mesh)(self.tree_to_rows, P(DATA_AXIS))(params)

    def assemble(self, stored):
        # FSDP-style assembly: this device's [1, shard] row -> the
        # full parameter tree, once per step (the all-gather of
        # ZeRO-3, as a vma-typed scatter+psum).
        return self.unravel(self.gather(stored))

    def _local_row(self, params, stored, start):
        return stored, None  # the local [1, shard] row, as stored

    def _store_row(self, new_row, unravel):
        # The updated row IS the new parameter state — no reassembly
        # collective at all (the next step's assembly does that work
        # once).
        return new_row

    def _rows_to_tree(self, rows):
        return self.unravel(jnp.asarray(_rows_to_flat(rows, self.n)))

    def full_params(self, stored):
        return self._tree_on_mesh(stored)

    def params_to_canonical(self, stored, run=on_host):
        return run(self._rows_to_tree, P())(stored)

    def params_from_canonical(self, tree, run=on_host):
        return run(
            lambda t: self.tree_to_rows(jax.tree.map(jnp.asarray, t)),
            P(DATA_AXIS),
        )(tree)


class Zero3Blocks(_DataSharded):
    """TRUE per-layer ZeRO-3/FSDP. Params — and every params-shaped
    mirror: optimizer moments, the GNS prev_grad carry — live as the
    rows dict

        {"blocks": [L, dp, shard_b], "other": [dp, shard_o]}

    sharded P(None, "data") / P("data"): each device persistently
    holds 1/dp of every tensor. The loss_fn (written against
    parallel.zero3.Zero3View) gathers ONE block at a time inside its
    layer scan — per-device peak HBM is params/dp + one gathered block
    + activations, where zero3-lite still materialises the whole tree
    at step start.

    The loss is differentiated directly with respect to this device's
    ROW storage. The forward gathers parameters (the non-block subtree
    once, each block inside the model's layer scan), so the AD
    transpose hands back cotangents that are already globally SUMMED
    over the data axis and scattered to each device's own rows —
    FSDP's reduce-scatter, for free. Two consequences:

    - No gradient pmean: dividing the row cotangent by dp IS the fully
      averaged gradient. The optimizer runs on local rows.
    - The GNS sees only per-microbatch GLOBAL gradients (the
      per-replica signal is consumed by the reduce-scatter), so
      ``count = num_microbatches`` — the estimator pairs batch sizes
      (dp*atomic, full) instead of (atomic, full) — and at accum_steps
      == 0 the differenced estimator takes over, its prev_grad carry
      LIVE at any dp and held in rows layout (n/dp per device).

    Canonical disk layouts match the zero1/zero3-lite family: params
    as the plain TREE, derived mirrors (the carry too: it holds the
    GLOBAL mean gradient, so it survives a dp change intact) as the
    flat [n] vector."""

    name = "zero3-blocks"
    reduces_gradient = False  # (the reduce-scatter inside AD, above)
    mirror_specs = {"blocks": P(None, DATA_AXIS), "other": P(DATA_AXIS)}
    _composes = (
        "zero3_blocks shards parameter storage over the "
        "data axis and composes with data and sequence "
        "parallelism only (model/stage/expert axes "
        "manage their own layouts)"
    )

    def __init__(self, *, blocks_key: str, **kwargs):
        super().__init__(**kwargs)
        if self.num_groups > 1:
            raise ValueError(
                "zero3_blocks supports a single param group (the "
                "row layout has no per-position group table yet)"
            )
        if blocks_key not in self.template:
            raise ValueError(
                f"params has no {blocks_key!r} entry to treat as "
                "the layer-stacked block family"
            )
        self.name = f"{self.name}:{blocks_key}"
        self.blocks_key = blocks_key
        self.spec = z3.block_spec(self._zeros(), blocks_key)
        self.shard_b, self.shard_o = z3.shard_sizes(self.spec, self.dp)
        self.group_ids = (0, 0)  # the rows dict's two leaves

    # ---- the fresh state and its specs --------------------------------

    def tree_to_rows(self, tree):
        """Canonical param tree -> rows dict (traceable)."""
        blocks_rows, other_rows = z3.tree_to_rows(
            jax.tree.map(jnp.asarray, tree),
            self.blocks_key, self.spec, self.dp,
        )
        return {"blocks": blocks_rows, "other": other_rows}

    def param_specs(self):
        return self.mirror_specs

    carry_specs = param_specs

    def build(self, params=None):
        rows = self.tree_to_rows(
            self._zeros() if params is None else params
        )
        return (
            rows,
            self.optimizer.init(rows),
            self._fresh_gns(rows),
        )

    def init(self, params):
        # Born sharded: one jit with rows out_shardings so params,
        # moments, and prev_grad land as [.., dp, shard] rows over
        # the data axis and never exist replicated on device. (The
        # init TREE itself is a replicated host constant — the
        # transient any fresh init or checkpoint load pays; the
        # per-STEP bound is what zero3-blocks guarantees.)
        _, opt_state, gns_state = jax.eval_shape(self.build)
        specs = (
            self.param_specs(),
            self._map_mirrors(
                opt_state, lambda _: self.mirror_specs, lambda _: P()
            ),
            jax.tree.map(lambda _: P(), gns_state)._replace(
                prev_grad=self.carry_specs()
            ),
        )
        return on_mesh(self.mesh)(
            functools.partial(self.build, params), specs
        )()

    # ---- inside the step's shard_map ----------------------------------

    @property
    def varying_axes(self):
        # A seq-sharded group is one logical replica whose members
        # hold pieces of the same batch rows; gathered values (and
        # activations) vary over both axes, but the rows and their
        # cotangents stay seq-invariant (the +seq pcast's transpose
        # psums the seq shards before the reduce-scatter).
        if self.seq_shards > 1:
            return (DATA_AXIS, SEQ_AXIS)
        return (DATA_AXIS,)

    def model_params(self, stored):
        # metric_fn receives the same Zero3View the loss_fn does: the
        # model's scan_blocks forward works unchanged and eval keeps
        # the per-block memory bound.
        return z3.build_view(
            stored["blocks"], stored["other"], self.spec,
            varying_axes=self.varying_axes,
        )

    def differentiable(self, params):
        return params

    def differentiated(self, loss_fn):
        # (wraps: a counting loss_fn keeps its ``has_counters``.)
        return functools.wraps(loss_fn)(
            lambda rows, *args: loss_fn(self.model_params(rows), *args)
        )

    def whole_sample(self, loss, grad):
        # The row cotangent is the SUM over every device (seq shards
        # psum'd by the pcast transpose, data replicas by the
        # reduce-scatter) of the per-device mean-loss gradient;
        # /(dp*sp) makes it this microbatch's global mean gradient.
        divisor = self.dp * self.seq_shards
        return loss, jax.tree.map(lambda g: g / divisor, grad)

    def precond(self, opt_state):
        # Adam's nu is a rows-dict mirror; this device's local rows
        # precondition this device's row-space gradients directly — no
        # reassembly (globally consistent: the rows ARE the true nu
        # shards).
        if self.precondition != "adam":
            return None
        return jax.tree.map(
            lambda v: jnp.sqrt(
                jnp.maximum(v.astype(jnp.float32), 0.0)
            )
            + 1e-8,
            _adam_nu(opt_state),
        )

    def micro_precond(self, precond):
        return precond

    def normsqr(self, tree, pre=None):
        # Each device's rows are a disjoint shard of the flat gradient,
        # so the psum of local squared norms is the global squared norm
        # (pad positions carry zero cotangent) — invariant after it.
        out = gns.group_normsqr(tree, self.group_ids, 1, pre)
        return jax.lax.psum(out, DATA_AXIS)

    def gns_count(self, num_micro: int) -> int:
        return num_micro

    def accumulators(self, params):
        return (
            jax.tree.map(
                lambda p: (p * 0.0).astype(jnp.float32), params
            ),
            jnp.zeros((1,)),
            jax.lax.pcast(jnp.zeros(()), self.varying_axes, to="varying"),
        )

    def reduce(self, grad_sum, lsqr_sum, loss_sum, num_micro: int):
        # Already globally averaged over replicas; average the
        # microbatches. No pmean — the collective already happened
        # inside AD.
        return (
            jax.tree.map(lambda g: g / num_micro, grad_sum),
            lsqr_sum / num_micro,
            jax.lax.pmean(loss_sum / num_micro, self.varying_axes),
        )

    # ---- outside the step ---------------------------------------------

    def _rows_to_tree(self, rows):
        return z3.rows_to_tree(
            jnp.asarray(rows["blocks"]), jnp.asarray(rows["other"]),
            self.blocks_key, self.spec,
        )

    def full_params(self, stored):
        return self._tree_on_mesh(stored)

    def params_to_canonical(self, stored, run=on_host):
        return run(self._rows_to_tree, P())(dict(stored))

    def params_from_canonical(self, tree, run=on_host):
        return run(self.tree_to_rows, self.mirror_specs)(tree)

    def _mirror_to_flat(self, rows):
        return z3.rows_to_flat_canonical(
            jnp.asarray(rows["blocks"]), jnp.asarray(rows["other"]),
            self.blocks_key, self.spec,
        )

    def _mirror_from_canonical(self, canon):
        """Accepts BOTH canonical layouts: flat [n] vectors (zero
        family checkpoints) and plain param trees (a dense trainer's
        checkpoint crossing into blocks mode)."""
        if not self._is_flat(canon):
            return self.tree_to_rows(canon)
        blocks_rows, other_rows = z3.flat_canonical_to_rows(
            canon, self.blocks_key, self.spec, self.dp, self.unravel
        )
        return {"blocks": blocks_rows, "other": other_rows}

    def carry_to_canonical(self, gns_state, run=on_host):
        return gns_state._replace(
            prev_grad=run(self._mirror_to_flat, P())(gns_state.prev_grad)
        )

    def carry_from_canonical(self, gns_state, run=on_host):
        if self._is_flat(gns_state.prev_grad):
            # Our canonical carry: the global mean gradient,
            # dp-independent — expand to this dp's rows.
            return gns_state._replace(
                prev_grad=run(
                    self._mirror_from_canonical, self.mirror_specs
                )(gns_state.prev_grad)
            )
        # Foreign layout (a dense/lite checkpoint crossing into blocks
        # mode): re-prime the differenced estimator.
        fresh, invalid = run(
            lambda: (
                jax.tree.map(
                    jnp.zeros_like, self.tree_to_rows(self._zeros())
                ),
                jnp.zeros((), bool),
            ),
            (self.mirror_specs, P()),
        )()
        return gns_state._replace(
            prev_grad=fresh, prev_grad_valid=invalid
        )


def resolve(
    *, zero1: bool, zero3: bool, zero3_blocks: str | None, **context
) -> Layout:
    """``ElasticTrainer``'s three storage arguments -> one layout.
    Like any of them the choice is part of the job's stable config:
    rescales change dp freely, not the layout."""
    if zero3_blocks is not None:
        if zero1 or zero3:
            raise ValueError(
                "zero3_blocks is a storage mode of its own; do not "
                "combine with zero1/zero3"
            )
        return Zero3Blocks(blocks_key=zero3_blocks, **context)
    if zero3:
        return Zero3Lite(**context)
    if zero1:
        return Zero1(**context)
    return Layout(**context)
