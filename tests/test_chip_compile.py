"""The chip's compiler, asked without the chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
PR 7's histogram and leaf kernels passed all of it and were refused by
the TPU compiler (rank-3 reshapes in VMEM). The TPU compiler is
installed here and compiles for a *described* v5e that is not attached,
so each Pallas kernel of the main path is compiled once at its published
width: HIGGS's 28 features, the Spark-parity depth-5 / 32-bin defaults,
rf's vmapped batch of 5 stat sets (stacked on the histogram kernel's
matmul rows; its leaf statistics a grid axis), the 256-bin uint8 extreme
at its own tile, the t-SNE repulsion at the 8,192-row plot size, and
the attention kernels of the ``tx`` family's query blocks at
Keye-VL-2.0's widths (rows of 8,192 tokens, 32 query / 4 key-value heads
of 128, blocks of 128 queries), forward and backward, and at the default
block of 512 queries with 8 and with 16 heads a key-value head, where
the kernels' shape rule sizes the key block to VMEM, and at
Olmo-Hybrid's full layer (15 held key-value heads of 128 with one query
head each). And one whole program: the training step of the benchmark's
``olmo-hybrid-7b`` configuration (one period, rows of 8,192 tokens, 15
heads held), whose state and temporaries have to fit the chip.
Between them the histogram cases take every form of the bin one-hot the
kernel's shape rule can choose (``pk._tree_hist_kernel``): one compare a
128-column group with four features a group (32 bins), with one feature
over two groups (256), with features that straddle groups and a width
that is no multiple of 128 (48), each alone and stacked; and the compare
per feature of the leaf statistics' 63 "bins".

Nothing runs — a pass here says nothing about results or times, and is
never reported as a chip run. This is the one file that describes a
topology: only one process may hold the TPU library, and the xdist
worker that is handed this file keeps it until it exits.
"""

from functools import partial

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from learningorchestra_tpu.ops import pallas_kernels as pk  # noqa: E402

#: Published widths (module docstring).
N, D, N_BINS, DEPTH, S = 1 << 20, 28, 32, 5, 2
NL = 2 ** (DEPTH - 1)                 # per-level node width (16)
M = 2 ** (DEPTH + 1) - 1              # nodes of a depth-5 tree (63)
HDT = jnp.bfloat16                    # trees._hist_dtype() on the chip
N_TSNE = 8192
#: The benchmark's ``keye-vl-2.0-30b-a3b`` attention: row, heads, block.
T_TX, H_TX, G_TX, D_TX, C_TX = 8192, 32, 4, 128, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def chip_compiler(monkeypatch):
    """Steer the kernels off interpret mode (the backend here is still
    the CPU) and keep the persistent compile cache out of it: a compile
    for a described device is written there but cannot be read back
    without a chip, and the next one would warn."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(pk, "mxu_operand_dtype", lambda: HDT)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _hist(n_bins):
    return (partial(pk.tree_histogram, n_nodes=NL, n_bins=n_bins,
                    tile=pk.tree_tile(D, n_bins), operand_dtype=HDT),
            [((D, N), jnp.uint8), ((S, N), jnp.float32),
             ((N,), jnp.int32), ((N,), jnp.bool_)])


def _hist_vmapped(n_bins=N_BINS):
    fn, (codes, stats, rel, act) = _hist(n_bins)
    # rf's batched build (trees._forest_batch_shape(20) → batches of 5):
    # stats and node ids carry the tree axis, the bin matrix is shared —
    # so the call's batching rule stacks the five trees on the kernel's
    # matmul rows (at 256 bins two a call: the accumulator budget).
    return (jax.vmap(fn, in_axes=(None, 0, 0, 0)),
            [codes, ((5,) + stats[0], stats[1]), ((5,) + rel[0], rel[1]),
             ((5,) + act[0], act[1])])


def _leaf(trees=None):
    # One synthetic feature, non-lane-aligned bin count (63). Under rf's
    # vmap the "codes" are each tree's own assignment: the grid form.
    lead = () if trees is None else (trees,)
    fn = partial(pk.tree_leaf_stats, n_nodes=M,
                 tile=pk.tree_tile(D, N_BINS), operand_dtype=HDT)
    return (fn if trees is None else jax.vmap(fn),
            [(lead + (N,), jnp.int32), (lead + (S, N), jnp.float32)])


def _route():
    node = ((NL,), jnp.int32)
    return (partial(pk.tree_route_level, tile=pk.tree_tile(D, N_BINS)),
            [((D, N), jnp.uint8), ((N,), jnp.int32), ((N,), jnp.bool_),
             ((N,), jnp.int32), node, node, ((NL,), jnp.bool_)])


def _descend(trees=None):
    lead = () if trees is None else (trees,)
    tbl = (lead + (M,), jnp.int32)
    fn = partial(pk.tree_descend, max_depth=DEPTH)
    if trees is not None:        # the forest predict: tables per tree
        fn = jax.vmap(fn, in_axes=(None, 0, 0, 0))
    return fn, [((D, N), jnp.uint8), tbl, tbl, (lead + (M,), jnp.bool_)]


def _tsne():
    return (pk.tsne_repulsion,
            [((N_TSNE, 2), jnp.float32), ((N_TSNE,), jnp.float32)])


def _tsne_rows():
    # One shard of the row-sharded descent on a four-chip host.
    nq = N_TSNE // 4
    return (pk.tsne_repulsion_rows,
            [((nq, 2), jnp.float32), ((nq,), jnp.float32),
             ((N_TSNE, 2), jnp.float32), ((N_TSNE,), jnp.float32),
             ((), jnp.int32)])


def _chosen_attention(backward: bool, heads: int = H_TX, chunk: int = C_TX,
                      key_block: int = 512, groups: int = G_TX):
    """One query block of ``transformer._chosen_attention`` on the
    kernels; the backward as the block's rematerialised forward and its
    transpose, as the step runs it. ``key_block``: what the shape rule
    is to choose for ``heads`` over ``groups`` key-value heads and a
    block of ``chunk`` queries."""
    assert pk.chosen_attn_key_block(T_TX, chunk, D_TX,
                                    heads // groups) == key_block

    def grads(q, k, v, chosen, block):
        return jax.grad(lambda q, k, v: pk.chosen_attention(
            q, k, v, chosen, block)[0].sum(), (0, 1, 2))(q, k, v)

    return (grads if backward else pk.chosen_attention,
            [((chunk, heads, D_TX), jnp.float32),
             ((T_TX, groups * D_TX), jnp.float32),
             ((T_TX, groups * D_TX), jnp.float32),
             ((chunk, T_TX), jnp.bool_), ((), jnp.int32)])


def _delta_transform():
    # One 256-token block of Olmo-Hybrid's linear mixer on this chip: 15
    # held heads x 4 chunks of 64 tokens, 60 systems a call.
    return pk.delta_transform, [((1, 15, 4, 64, 64), jnp.float32)]


def _grouped(which: str, d: int, f: int, held: int, rows: int):
    """One token block's grouped product of the expert layer: a block of
    1,024 tokens has at most ``rows`` assignments to the ``held``
    experts of width ``f`` on this chip, whatever of them are real."""
    sizes = ((held,), jnp.int32)
    if which == "up":
        return (pk.grouped_matmul, [((rows, d), HDT), ((held, d, f), HDT),
                                    sizes])
    if which == "dx":
        return (partial(pk.grouped_matmul, transpose=True),
                [((rows, f), HDT), ((held, d, f), HDT), sizes])
    return pk.grouped_matmul_t, [((rows, d), HDT), ((rows, f), HDT), sizes]


CASES = {
    "chosen_attention-forward": lambda: _chosen_attention(False),
    "chosen_attention-backward": lambda: _chosen_attention(True),
    # ``TxConfig.q_chunk``'s default, 512 queries a block: 8 heads a
    # group (Keye-VL's own) keep the key block of 512; 16 fit VMEM with
    # one of 128 (``pk._attn_vmem_bytes``).
    "chosen_attention-forward-8heads-a-group-512queries":
        lambda: _chosen_attention(False, 32, 512, 512),
    "chosen_attention-backward-8heads-a-group-512queries":
        lambda: _chosen_attention(True, 32, 512, 512),
    "chosen_attention-forward-16heads-a-group-512queries":
        lambda: _chosen_attention(False, 64, 512, 128),
    "chosen_attention-backward-16heads-a-group-512queries":
        lambda: _chosen_attention(True, 64, 512, 128),
    # Olmo-Hybrid's full layer on one chip of two: multi-head attention,
    # R = 1, 15 of the 30 heads held, blocks of 1,024 queries.
    "chosen_attention-forward-1head-a-group-15groups-1024queries":
        lambda: _chosen_attention(False, 15, 1024, 512, 15),
    "chosen_attention-backward-1head-a-group-15groups-1024queries":
        lambda: _chosen_attention(True, 15, 1024, 512, 15),
    "delta_transform": _delta_transform,
    # Keye-VL's experts (16 held of width 768, top-8) and Nemotron's (8
    # held of width 1,856, top-6, a width no multiple of 128), hidden
    # 2,048 / 2,688: up-projection, its input gradient, weight gradient.
    **{f"grouped_matmul-{w}-{m}": partial(_grouped, w, *dims)
       for m, dims in (("keye", (2048, 768, 16, 8192)),
                       ("nemotron", (2688, 1856, 8, 6144)))
       for w in ("up", "dx", "dw")},
    "tree_histogram-32bins": lambda: _hist(32),
    "tree_histogram-256bins": lambda: _hist(256),
    "tree_histogram-48bins-straddling": lambda: _hist(48),
    "tree_histogram-vmap5": _hist_vmapped,
    "tree_histogram-vmap5-256bins": lambda: _hist_vmapped(256),
    "tree_histogram-vmap5-48bins-straddling": lambda: _hist_vmapped(48),
    "tree_leaf_stats": _leaf,
    "tree_leaf_stats-vmap5": lambda: _leaf(5),
    "tree_route_level": _route,
    "tree_descend": _descend,
    "tree_descend-vmap20": lambda: _descend(20),
    "tsne_repulsion": _tsne,
    "tsne_repulsion_rows": _tsne_rows,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, chip_compiler):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: The name each case's kernel carries on a device profile's ``XLA Ops``
#: line (``tree_leaf_stats`` shares the histogram's ``pallas_call``).
KERNEL_NAMES = {
    "delta_transform": "delta_transform",
    "grouped_matmul-up-keye": "grouped_mm",
    "grouped_matmul-dx-nemotron": "grouped_mm",
    "grouped_matmul-dw-nemotron": "grouped_mm_t",
    "tree_histogram-32bins": "tree_hist",
    "tree_histogram-256bins": "tree_hist",
    "tree_histogram-48bins-straddling": "tree_hist",
    "tree_histogram-vmap5": "tree_hist_stacked",
    "tree_histogram-vmap5-48bins-straddling": "tree_hist_stacked",
    "tree_histogram-vmap5-256bins": "tree_hist",
    "tree_leaf_stats": "tree_hist",
    "tree_leaf_stats-vmap5": "tree_hist",
    "tree_route_level": "tree_route",
    "tree_descend": "tree_descend",
    "tree_descend-vmap20": "tree_descend",
    "tsne_repulsion": "tsne_repulsion",
}


@pytest.mark.parametrize("case", sorted(KERNEL_NAMES))
def test_kernel_is_named_in_the_compiled_module(case, one_chip,
                                                chip_compiler):
    """The profiler names an ``XLA Ops`` event by the instruction's text
    up to its frontend attributes (no ``metadata=``, no backend
    config), so the kernel's name has to be the custom call's own
    instruction name, vmapped or not: what ``hist_kernel_s.sweep`` and
    ``route_kernel_s.sweep`` match, in one expression with the call
    target so a fusion that inherits the scope is never counted."""
    import re

    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    # vmap wraps the scope: ``%vmap_tree_hist_.1``.
    named = re.compile(r"%[^ ]*" + KERNEL_NAMES[case] + r"[^ ]* = .*"
                       r'custom_call_target="tpu_custom_call"')
    assert calls and all(named.search(ln) for ln in calls), \
        [ln[:80] for ln in calls]
    metric = {"tree_hist": "hist_kernel_s.sweep",
              "tree_hist_stacked": "hist_kernel_s.sweep",
              "tree_route": "route_kernel_s.sweep"}.get(KERNEL_NAMES[case])
    if metric:           # the benchmark's own expression finds them too
        import json
        import os

        with open(os.path.join(os.path.dirname(__file__), os.pardir,
                               "perfbench", "layer_metrics",
                               metric + ".json")) as fh:
            (pattern,) = json.load(fh)["ops"]
        assert all(re.search(pattern, ln) for ln in calls)


@pytest.mark.parametrize("case,kernels,metrics", [
    ("chosen_attention-forward", ["chosen_attn_fwd", "chosen_attn_probs"],
     ("sparse_attn_s.txfit", "sparse_attn_roofline.txfit", "moe_s.txfit")),
    ("chosen_attention-backward", ["chosen_attn_fwd", "chosen_attn_bwd"],
     ("sparse_attn_s.txfit", "sparse_attn_roofline.txfit", "moe_s.txfit")),
    ("delta_transform", ["delta_transform"],
     ("linear_attn_s.hybridfit", "linear_attn_roofline.hybridfit",
      "full_attn_s.hybridfit")),
    ("grouped_matmul-up-keye", ["grouped_mm"],
     ("moe_s.txfit", "moe_s.ssmfit")),
    ("grouped_matmul-dw-keye", ["grouped_mm_t"],
     ("moe_s.txfit", "moe_s.ssmfit")),
])
def test_attention_kernels_are_counted_once(case, kernels, metrics, one_chip,
                                            chip_compiler):
    """The attention kernels run INSIDE the query-block loops that
    ``sparse_attn_s.txfit``'s first pattern matches, and the reader sums
    its matches: a kernel there whose own name held ``sparse_attn``
    would be counted twice. Each is named for what it is, and neither
    of the metric's patterns (nor the expert layer's) finds it. The
    same holds for the chunk transform's kernel inside the linear
    mixers' block loops and ``linear_attn_s.hybridfit``'s patterns, and
    for the grouped products inside the expert layers' token-block
    loops and ``moe_s``'s (whose second pattern takes a ``moe_``
    kernel)."""
    import json
    import os
    import re

    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == len(kernels)
    for ln, kernel in zip(calls, kernels):
        assert re.match(r"(ROOT )?%[^ ]*" + kernel + r"[^ ]* = ", ln), ln[:80]
    for metric in metrics:
        with open(os.path.join(os.path.dirname(__file__), os.pardir,
                               "perfbench", "layer_metrics",
                               metric + ".json")) as fh:
            patterns = json.load(fh)["ops"]
        assert not any(re.search(p, ln) for p in patterns for ln in calls)


@pytest.mark.parametrize("family", ["forest", "gbt"])
def test_tree_predict_compiles_on_four_chips(family, topo, chip_compiler):
    """The batch predict of a row-sharded design on the 2x2 host: XLA
    cannot partition a Mosaic kernel by itself (the first four-chip run
    failed on exactly that), so the predict programs run the descent
    kernel under shard_map over the design's mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from learningorchestra_tpu.config import Settings
    from learningorchestra_tpu.models import trees
    from learningorchestra_tpu.parallel.mesh import DATA_AXIS, local_mesh

    mesh = local_mesh(Settings(), devices=topo.devices)
    rep = NamedSharding(mesh, P())

    def shape(dims, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    trees_n = 20
    params = {"edges": shape((D, N_BINS - 1), jnp.float32),
              "feat": shape((trees_n, M), jnp.int32),
              "thr": shape((trees_n, M), jnp.int32),
              "internal": shape((trees_n, M), jnp.bool_)}
    if family == "forest":
        fn = trees._forest_proba_static
        params["leaf"] = shape((trees_n, M, 2), jnp.float32)
    else:
        fn = trees._gbt_proba_static
        params["leaf_val"] = shape((trees_n, M), jnp.float32)
        params["step_size"] = shape((), jnp.float32)
    X = shape((100_000, D), jnp.float32,
              NamedSharding(mesh, P(DATA_AXIS, None)))
    compiled = fn.program.lower(params, X, max_depth=DEPTH,
                                mesh=mesh).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _cell(name):
    """``(cfg, conf)``: the benchmark's configuration ``name`` as its
    cell POSTs it."""
    import json
    import os

    from learningorchestra_tpu.models import transformer as tx

    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "perfbench", "configs", name + ".json")) as fh:
        conf = json.load(fh)
    hp, rows = conf["families"]["tx"], conf["data"]["seq_len"]
    return tx.TxConfig(
        vocab=hp["vocab"], d_model=hp["d_model"], n_heads=hp["n_heads"],
        n_layers=hp["n_layers"], n_classes=conf["data"]["num_classes"],
        max_len=rows, causal=hp["causal"], remat=hp["remat"],
        **hp["arch"]), conf


def _matcher(lines):
    """``matched(metric)``: the lines a per-layer metric's patterns
    find."""
    import json
    import os
    import re

    def matched(metric):
        with open(os.path.join(os.path.dirname(__file__), os.pardir,
                               "perfbench", "layer_metrics",
                               metric + ".json")) as fh:
            patterns = json.load(fh)["ops"]
        return [ln for ln in lines if any(re.search(p, ln) for p in patterns)]

    return matched


def _compiled_step(topo, name):
    """The training step of the benchmark's configuration ``name`` as
    its cell POSTs it, compiled for one chip of the described v5e:
    ``(cfg, conf, held parameters, compiled, loops, matched)``;
    ``loops`` the compiled ``while`` lines, ``matched(metric)`` those a
    per-layer metric's patterns find."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from learningorchestra_tpu.config import Settings
    from learningorchestra_tpu.models import transformer as tx
    from learningorchestra_tpu.parallel.mesh import local_mesh

    cfg, conf = _cell(name)
    hp, rows = conf["families"]["tx"], conf["data"]["seq_len"]
    settings = Settings()
    settings.mesh_shape = "1,1,1"
    mesh = local_mesh(settings, devices=topo.devices[:1])
    init, step = tx.make_fit_programs(cfg, mesh, optax.adam(hp["lr"]),
                                      hp["batch"])
    rep = NamedSharding(mesh, P())

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=rep), tree)

    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    held = sum(a.size for a in jax.tree.leaves(state[0]))
    n = conf["data"]["n_train"]
    compiled = step.lower(
        placed(state), placed(jax.eval_shape(jax.random.PRNGKey, 0)),
        jax.ShapeDtypeStruct((n, rows), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rep)).compile()
    loops = [ln.strip() for ln in compiled.as_text().splitlines()
             if " while(" in ln]
    return cfg, conf, held, compiled, loops, _matcher(loops)


def _hbm():
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "perfbench", "peaks.json")) as fh:
        return json.load(fh)["devices"]["TPU v5 lite"]["hbm_bytes"]


def _loop_kernels(text: str, loop: str) -> str:
    """The text of every computation a ``while`` line's body reaches
    (its body, and what that calls or branches to)."""
    import re

    comps, name, body = {}, None, []
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[^ ]+) .*\{$", ln)
        if head:
            name, body = head.group(1), []
        elif ln.startswith("}") and name:
            comps[name], name = "\n".join(body), None
        elif name:
            body.append(ln)
    seen, todo = set(), [re.search(r"body=(%[^ ,]+)", loop).group(1)]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += re.findall(r"(?:calls|to_apply|body|condition)=(%[^ ,}]+)",
                           comps[c])
        for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                comps[c]):
            todo += [b.strip() for b in group.split(",")]
    return "\n".join(comps[c] for c in seen)


def _expert_loops_run_the_kernels(compiled, moe, backward=True):
    """Every expert-layer loop ``moe_s`` matches reaches the grouped
    products' custom calls (the routed pairs are computed there), and
    with ``backward`` some reach the weight gradients' (``grouped_mm_t``)."""
    import re

    text = compiled.as_text()
    inner = [_loop_kernels(text, loop) for loop in moe]
    for loop, body in zip(moe, inner):
        assert re.search(r"%grouped_mm[.0-9]* = [^\n]*tpu_custom_call",
                         body), loop[:80]
    assert backward == any(re.search(
        r"%grouped_mm_t[.0-9]* = [^\n]*tpu_custom_call", b) for b in inner)


def test_hybrid_step_compiles_and_fits_the_chip(topo, chip_compiler):
    """The training step of the benchmark's ``olmo-hybrid-7b``
    configuration as the cell POSTs it (one period LLLF, rows of 8,192
    tokens, 15 of 30 heads held, MLP 11,008 whole, 12,544 vocabulary
    rows), compiled for the described v5e: its state and its temporaries
    together are under the device's memory, and its loops are named as
    the cell's device-trace metrics expect them (the linear mixers'
    block loops by the carried state, three a linear layer: forward,
    rematerialised forward, backward; the full layer's query-block
    loops), none matched by the other's pattern."""
    import re

    cfg, conf, held, compiled, loops, matched = _compiled_step(
        topo, "olmo-hybrid-7b")
    assert held == conf["state"]["parameters"]
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 0.75 * 16 * held   # w, m, v
    assert total < _hbm(), (total, mem.temp_size_in_bytes)
    text = compiled.as_text()
    # PR 37: the chunk transform is built by blocks; XLA's triangular
    # solve (a serial ``InvertDiagBlocksLowerTriangular`` custom call a
    # block and pass) is in the program no more.
    assert not re.search(r"InvertDiagBlocks|triangular[-_]solve", text)
    core = matched("linear_attn_s.hybridfit")
    full = matched("full_attn_s.hybridfit")
    # The transform's kernel: once in each of a linear layer's loops
    # (the backward loop rematerialises its block's forward, then takes
    # the transform's cotangent through two products).
    assert len(re.findall(r"%[^ ]*delta_transform[^ ]* = [^\n]*"
                          r'custom_call_target="tpu_custom_call"', text)) \
        == len(core)
    assert len(core) == 3 * cfg.pattern.count("L")
    assert len(full) >= 2 and not set(core) & set(full)
    assert "tpu_custom_call" in text                    # the full layer's


def test_ssm_step_compiles_and_fits_the_chip(topo, chip_compiler):
    """The training step of the benchmark's ``nemotron-labs-twotower-
    30b-a3b`` configuration as the cell POSTs it (one period MEMEMFEME,
    two rows of 8,192 tokens a step, 8 of 128 experts held, 16,384
    vocabulary rows), compiled for the described v5e: its peak, state
    and temporaries by the compiler's heap simulation, is under the
    device's memory (15.85 GB; the sum of its temporary allocations
    beside its arguments, 19.2 GB, counts buffers that are never live
    together, and the chip runs the step), and its loops
    are named as the cell's device-trace metrics expect them (the
    Mamba-2 mixers' block loops by the carried state, three an M layer:
    forward, rematerialised forward, backward; the expert layers'
    window loops by the held experts' weights, forward and backward,
    each of them running the grouped products; the attention
    layer's query-block loops by their stacked outputs), none matched by
    another's pattern."""
    cfg, conf, held, compiled, loops, matched = _compiled_step(
        topo, "nemotron-labs-twotower-30b-a3b")
    # the parameters, and the 4 x 128 correction bias (a buffer)
    assert held == conf["state"]["parameters"] + 4 * 128
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0.75 * 16 * held   # w, m, v
    assert mem.argument_size_in_bytes < mem.peak_memory_in_bytes < _hbm(), (
        mem.peak_memory_in_bytes, mem.temp_size_in_bytes)
    core, moe = matched("ssm_s.ssmfit"), matched("moe_s.ssmfit")
    attn = matched("full_attn_s.ssmfit")
    assert len(core) == 3 * cfg.pattern.count("M")
    # the window loops of each E layer, forward and backward, and no
    # loop inside them (the window turn is a conditional)
    assert len(moe) == 2 * cfg.pattern.count("E")
    assert not set(core) & set(moe)
    assert len(attn) >= 2 and not set(attn) & (set(core) | set(moe))
    _expert_loops_run_the_kernels(compiled, moe)


def test_keye_step_compiles_and_fits_the_chip(topo, chip_compiler):
    """The training step of the benchmark's ``keye-vl-2.0-30b-a3b``
    configuration as the cell POSTs it (6 layers, one row of 8,192
    tokens a step, 16 of 128 experts held, 18,992 vocabulary rows),
    compiled for the described v5e: its peak by the compiler's heap
    simulation is under the device's memory, the expert layer's
    window loops, forward and backward (the rematerialised forward's is
    not kept: the layer's backward needs only its inputs), are the
    only ones ``moe_s.txfit`` matches by the held experts' weights, each
    runs the grouped products, and none is an attention loop."""
    cfg, conf, held, compiled, loops, matched = _compiled_step(
        topo, "keye-vl-2.0-30b-a3b")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0.75 * 16 * held   # w, m, v
    assert mem.argument_size_in_bytes < mem.peak_memory_in_bytes < _hbm(), (
        mem.peak_memory_in_bytes, mem.temp_size_in_bytes)
    moe, attn = matched("moe_s.txfit"), matched("sparse_attn_s.txfit")
    assert len(moe) == 2 and not set(moe) & set(attn)
    _expert_loops_run_the_kernels(compiled, moe)


@pytest.mark.parametrize("name,metric", [
    ("keye-vl-2.0-30b-a3b", "moe_s.txfit"),
    ("nemotron-labs-twotower-30b-a3b", "moe_s.ssmfit")])
def test_expert_predict_loops_are_matched_once(name, metric, topo,
                                               chip_compiler):
    """The predict pass of the cell's 16 test rows, compiled for one
    chip of the described v5e: ``moe_s`` finds each expert layer's
    window loop once, each running the grouped products (no weight
    gradient), and no loop inside one. Where the layers are not
    stacked (Nemotron's period), the map over the rows carries every
    parameter as it is, the held experts' weights among them, so the
    pattern finds that loop too, as it did before the grouped path."""
    from jax.sharding import SingleDeviceSharding

    from learningorchestra_tpu.models import sequence
    from learningorchestra_tpu.models import transformer as tx

    cfg, conf = _cell(name)
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda k: tx.init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    X = jax.ShapeDtypeStruct((conf["data"]["n_test"], cfg.max_len),
                             jnp.int32, sharding=one)
    compiled = sequence._proba_program(cfg).lower(params, X).compile()
    loops = [ln.strip() for ln in compiled.as_text().splitlines()
             if " while(" in ln]
    moe = _matcher(loops)(metric)
    windows = [ln for ln in moe if "pred[" in ln]     # their activity
    if cfg.pattern:
        assert len(windows) == cfg.pattern.count("E") == len(moe) - 1
    else:
        assert len(windows) == len(moe) == 1
    _expert_loops_run_the_kernels(compiled, windows, backward=False)


def test_grouped_experts_compile_split_over_four_chips(topo, chip_compiler):
    """Keye-VL's expert layer with its 16 held experts split over a
    model axis of four chips of the described v5e (four a chip), forward
    and backward inside ``shard_map``: the grouped products, typed for
    the mesh, compile there, and the shards' partial outputs and the
    input's and router's cotangents are summed across the chips."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from learningorchestra_tpu.models import transformer as tx
    from learningorchestra_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, SEQ_AXIS)

    cfg, _ = _cell("keye-vl-2.0-30b-a3b")
    d, f, T = cfg.d_model, cfg.expert_width, cfg.max_len
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4, 1),
                (DATA_AXIS, MODEL_AXIS, SEQ_AXIS))
    specs = {"router": P(), "we_gate": P(MODEL_AXIS),
             "we_up": P(MODEL_AXIS), "we_down": P(MODEL_AXIS)}
    ax = tx.Axes(model=MODEL_AXIS)

    def loss(h, lyr):
        out = tx._experts(cfg, ax, h, lyr)[0]
        return jax.lax.psum((out.astype(jnp.float32) ** 2).sum(),
                            MODEL_AXIS)

    step = jax.jit(jax.grad(jax.shard_map(
        loss, mesh=mesh, in_specs=(P(), specs), out_specs=P()), (0, 1)))
    held = cfg.experts_held
    shapes = {"router": (d, cfg.n_experts), "we_gate": (held, d, f),
              "we_up": (held, d, f), "we_down": (held, f, d)}
    lyr = {k: jax.ShapeDtypeStruct(v, jnp.float32,
                                   sharding=NamedSharding(mesh, specs[k]))
           for k, v in shapes.items()}
    h = jax.ShapeDtypeStruct((1, T, d), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    text = step.lower(h, lyr).compile().as_text()
    for kernel in ("grouped_mm", "grouped_mm_t"):
        assert re.search(r"%" + kernel + r"[.0-9]* = [^\n]*tpu_custom_call",
                         text), kernel
    assert "all-reduce" in text
