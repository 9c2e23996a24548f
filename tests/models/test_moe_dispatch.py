"""The three ways ``_moe_mlp`` enumerates its experts' products
(``models/transformer.py``): ``grouped`` and, for a decode step,
``touched`` (the Pallas kernels of ``ops/grouped_matmul.py``, here in
interpret mode) held against ``dense`` and against a per-pair NumPy
reference built from the router's own choices; the chooser as a pure
function; and the benchmark's check 3 in small: prefill through grouped
experts against decode through the cache, dense and touched.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models.config import get_config
from gpustack_tpu.models.quant import QuantW, quantize_params
from gpustack_tpu.models.transformer import (
    GROUPED_MIN_FILL,
    KVCache,
    _moe_mlp,
    _route,
    forward,
    init_params,
    moe_dispatch,
)
from gpustack_tpu.ops import grouped_matmul as gm

E, K, D, F = 8, 2, 32, 48
BASE = dataclasses.replace(
    get_config("tiny-moe"), hidden_size=D, moe_intermediate_size=F,
    num_experts=E, num_experts_per_tok=K, dtype="float32",
)
SCORINGS = {
    "softmax": BASE,
    "sigmoid_bias": dataclasses.replace(
        BASE, moe_scoring="sigmoid", routed_scaling_factor=2.5
    ),
    "gptoss": dataclasses.replace(
        BASE, moe_scoring="softmax_topk", moe_act="gptoss", moe_bias=True
    ),
    # Nemotron-H: the DeepSeek-V3 router over two-matrix experts, no gate
    "relu2": dataclasses.replace(
        BASE, moe_scoring="sigmoid", routed_scaling_factor=2.5,
        moe_act="relu2",
    ),
}


def _plain(cfg, w, kw):
    """A gated layer's tensors as the plain form takes them: no gate
    matrix, in the routed experts or in the shared one."""
    if cfg.moe_act != "relu2":
        return w, kw
    w = {**w, "we_gate": None}
    if "shared" in kw:
        kw = {**kw, "shared": (None,) + tuple(kw["shared"][1:])}
    return w, kw

# rows an expert: none a multiple of the 128-row tile; "one" and "none"
# push an expert past one tile
ROUTINGS = {"even": 77, "one_takes_all": 150, "several_take_none": 300}


def _layer(scoring: str, routing: str, shared: bool, int8: bool, rows: int):
    cfg = SCORINGS[scoring]
    ks = jax.random.split(jax.random.key(len(scoring) + rows), 12)
    dtype = jnp.bfloat16 if int8 else jnp.float32
    x = jax.random.normal(ks[0], (1, rows, D), jnp.float32)
    # feature 0 is held at 1, so the router's first row is a bias an
    # expert: the routing is forced through the router's weights
    x = x.at[..., 0].set(1.0).astype(dtype)
    router = jax.random.normal(ks[1], (D, E), jnp.float32) * 0.05
    if routing == "one_takes_all":      # expert 3 is every token's first
        router = router.at[0, 3].set(50.0)
    elif routing == "several_take_none":
        router = router.at[0, jnp.array([0, 2, 5, 6])].set(-50.0)
    w = {
        "we_gate": jax.random.normal(ks[2], (E, D, F)) * 0.2,
        "we_up": jax.random.normal(ks[3], (E, D, F)) * 0.2,
        "we_down": jax.random.normal(ks[4], (E, F, D)) * 0.2,
    }
    if int8:
        w = quantize_params({"layers": {
            k: v[None] for k, v in w.items()
        }})["layers"]
        w = {k: QuantW(q=v.q[0], s=v.s[0]) for k, v in w.items()}
    kw = {}
    if scoring != "softmax":
        kw["router_bias"] = jax.random.normal(ks[5], (E,)) * 0.05
    if cfg.moe_bias:
        kw["biases"] = tuple(
            (jax.random.normal(k, (E, n)) * 0.3).astype(dtype)
            for k, n in zip(ks[6:9], (F, F, D))
        )
    if shared:
        kw["shared"] = (
            (jax.random.normal(ks[9], (D, F)) * 0.2).astype(dtype),
            (jax.random.normal(ks[10], (D, F)) * 0.2).astype(dtype),
            (jax.random.normal(ks[11], (F, D)) * 0.2).astype(dtype),
            None,
        )
    w, kw = _plain(cfg, w, kw)
    return cfg, x, router.astype(dtype), w, kw


def _reference(cfg, x, top_idx, top_w, w, kw, first=0):
    """Σ over a token's chosen experts, pair by pair, in float64; under
    a share whose ids start at ``first``, over the held ones of them."""
    def deq(m):
        if isinstance(m, QuantW):
            return np.asarray(m.q, np.float64) * np.asarray(
                m.s.astype(jnp.float32), np.float64
            )[:, None, :]
        return np.asarray(m, np.float64)

    xs = np.asarray(x.astype(jnp.float32), np.float64)[0]
    wu, wd = (deq(w[n]) for n in ("we_up", "we_down"))
    # the plain form (relu2) has no gate: its place is never read
    wg = deq(w["we_gate"]) if w["we_gate"] is not None else wu
    held = len(wg)
    bg, bu, bd = (
        (np.asarray(b.astype(jnp.float32), np.float64) for b in kw["biases"])
        if "biases" in kw
        else (np.zeros((held, F)),) * 2 + (np.zeros((held, D)),)
    )
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))  # noqa: E731
    out = np.zeros_like(xs)
    for t in range(xs.shape[0]):
        for e, wt in zip(np.asarray(top_idx)[0, t], np.asarray(top_w)[0, t]):
            e = e - first
            if not 0 <= e < len(wg):
                continue
            g, u = xs[t] @ wg[e] + bg[e], xs[t] @ wu[e] + bu[e]
            if cfg.moe_act == "gptoss":
                g, u = np.minimum(g, 7.0), np.clip(u, -7.0, 7.0)
                h = (u + 1.0) * g * sig(1.702 * g)
            elif cfg.moe_act == "relu2":
                h = np.maximum(u, 0.0) ** 2
            else:
                h = g * sig(g) * u
            out[t] += wt * (h @ wd[e] + bd[e])
    out *= cfg.routed_scaling_factor
    if "shared" in kw:
        su, sd = (
            np.asarray(m.astype(jnp.float32), np.float64)
            for m in kw["shared"][1:3]
        )
        if kw["shared"][0] is None:
            out += (np.maximum(xs @ su, 0.0) ** 2) @ sd
        else:
            a = xs @ np.asarray(
                kw["shared"][0].astype(jnp.float32), np.float64
            )
            out += (a * sig(a) * (xs @ su)) @ sd
    return out


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
@pytest.mark.parametrize(
    "scoring,shared",
    [("softmax", False), ("softmax", True), ("sigmoid_bias", True),
     ("gptoss", False), ("relu2", True)],
)
def test_grouped_equals_dense_pair_by_pair(scoring, shared, routing, int8):
    rows = ROUTINGS[routing]
    cfg, x, router, w, kw = _layer(scoring, routing, shared, int8, rows)

    def run(dispatch):
        return jax.jit(lambda x: _moe_mlp(
            x, router, w["we_gate"], w["we_up"], w["we_down"], cfg,
            dispatch=dispatch, **kw,
        ))(x)

    top_idx, top_w = jax.jit(
        lambda x: _route(x, router, cfg, kw.get("router_bias"))
    )(x)
    sizes = np.bincount(np.asarray(top_idx).ravel(), minlength=E)
    if routing == "one_takes_all":      # more than one tile of one expert
        assert sizes[3] == rows > gm.BLOCK_M
    elif routing == "several_take_none":
        assert (sizes[[0, 2, 5, 6]] == 0).all() and sizes.max() > gm.BLOCK_M
    else:
        assert (sizes > 0).all()

    dense, grouped = run("dense"), run("grouped_interpret")
    # the same input twice gives the same bits
    assert np.array_equal(
        np.asarray(grouped, np.float32),
        np.asarray(run("grouped_interpret"), np.float32),
    )
    # both sum exactly the router's pairs with the router's weights
    ref = _reference(cfg, x, top_idx, top_w, w, kw)
    top = np.abs(ref).max()
    # float32 weights: float32 rounding. int8 weights under bf16
    # activations: the products' roundings to bf16 (2**-8 each: g, u, h,
    # y, out); dense also rounds the combine weights, grouped does not
    bound = 2e-5 if not int8 else 2.0 ** -6
    for name, got in (("dense", dense), ("grouped", grouped)):
        err = np.abs(np.asarray(got, np.float64)[0] - ref).max() / top
        assert err < bound, (name, err)
    diff = np.abs(
        np.asarray(grouped, np.float64) - np.asarray(dense, np.float64)
    ).max() / top
    assert diff < bound


@pytest.mark.parametrize("pairs,groups", [(1, 4), (37, 4), (600, 8), (300, 128)])
@pytest.mark.parametrize("routing", ["even", "one", "none"])
def test_rows_are_grouped_padded_to_tiles_and_found_again(
    pairs, groups, routing
):
    """``group_rows``: every pair sits in a tile of its own group, in
    its group in the order it came, padding rows name no pair, tiles past
    the last real one are counted out, and ``dest`` finds each pair."""
    rng = np.random.default_rng(pairs + groups)
    ids = rng.integers(0, groups, pairs)
    if routing == "one":
        ids[:] = groups - 1
    elif routing == "none":
        ids = ids // 2 * 2 % groups       # odd groups take nothing
    rows = jax.jit(
        lambda g: gm.group_rows(g, groups)
    )(jnp.asarray(ids, jnp.int32))
    src, dest = np.asarray(rows.src), np.asarray(rows.dest)
    tile_group, n_active = np.asarray(rows.tile_group), int(rows.n_active[0])
    assert src.shape == (gm.padded_rows(pairs, groups),)
    assert (src[dest] == np.arange(pairs)).all()
    assert sorted(src[src < pairs]) == list(range(pairs))
    assert n_active == sum(
        -(-int(n) // gm.BLOCK_M) for n in np.bincount(ids, minlength=groups)
    )
    assert (src[n_active * gm.BLOCK_M:] == pairs).all()
    at = 0
    for t in range(n_active):
        held = src[t * gm.BLOCK_M:(t + 1) * gm.BLOCK_M]
        real = held[held < pairs]
        assert len(real) and (ids[real] == tile_group[t]).all()
        assert (held[:len(real)] == real).all()     # padding comes last
        assert (np.diff(real) > 0).all()            # stable
        at += len(real)
    assert at == pairs and (np.diff(tile_group[:n_active]) >= 0).all()


def _mesh(**axes):
    sizes = {"dp": 1, "sp": 1, "ep": 1, "tp": 1, **axes}
    n = 1
    for v in sizes.values():
        n *= v
    return types.SimpleNamespace(shape=sizes, size=n)


QWEN3_MOE = dataclasses.replace(BASE, num_experts=128, num_experts_per_tok=8)
DIFFUSION = {
    "diffusion_block": 4, "denoising_steps": 4,
    "remasking_strategy": "sequential", "mask_token_id": 7,
}


@pytest.mark.parametrize(
    "change,rows,platform,mesh,decode,want",
    [
        ({}, 32, "tpu", _mesh(), False, "dense"),     # no cache, or T > 1
        ({}, 4 * 32, "tpu", _mesh(), False, "dense"),  # a verify step's rows
        ({}, 128, "tpu", _mesh(), False, "dense"),    # 8 pairs an expert
        ({}, 256, "tpu", _mesh(), False, "grouped"),
        ({}, 1024, "tpu", _mesh(), False, "grouped"),
        ({}, 2048, "tpu", _mesh(), False, "grouped"),
        ({}, 2048, "tpu", None, False, "grouped"),    # no mesh: one device
        ({}, 2048, "tpu", _mesh(ep=4), False, "dense"),
        ({}, 2048, "tpu", _mesh(tp=2), False, "dense"),
        ({}, 2048, "tpu", _mesh(fsdp=2), False, "dense"),
        ({}, 2048, "tpu", _mesh(dp=2), False, "dense"),   # any mesh of several
        ({}, 2048, "cpu", _mesh(), False, "dense"),   # no compiled kernel
        ({}, 2048, "gpu", None, False, "dense"),
        # a decode step, one row a slot over the cache (PR 43)
        ({}, 32, "tpu", _mesh(), True, "touched"),
        ({}, 32, "tpu", None, True, "touched"),
        ({}, 1, "tpu", _mesh(), True, "touched"),     # one slot
        ({}, 2048, "tpu", _mesh(), True, "touched"),  # no row count decides
        ({"experts_held": 12, "num_experts": 192}, 16, "tpu", None, True,
         "touched"),                                  # under a share
        ({}, 32, "tpu", _mesh(ep=4), True, "dense"),
        ({}, 32, "tpu", _mesh(tp=2), True, "dense"),
        ({}, 32, "tpu", _mesh(dp=2), True, "dense"),
        ({}, 32, "cpu", _mesh(), True, "dense"),
        ({}, 32, "gpu", None, True, "dense"),
        # a model the kernel refuses (GPT-OSS): dense, and its prefill
        # grouped as before
        ({"moe_bias": True}, 32, "tpu", None, True, "dense"),
        ({"moe_act": "gptoss"}, 32, "tpu", None, True, "dense"),
        ({"moe_act": "gptoss", "moe_bias": True}, 2048, "tpu", None, False,
         "grouped"),
        # a diffusion model's block pass, 32 slots of 4 rows over the
        # cache: 8 pairs an expert, touched and not dense (PR 63)
        (DIFFUSION, 4 * 32, "tpu", _mesh(), True, "touched"),
        (DIFFUSION, 4 * 32, "tpu", None, True, "touched"),
        (DIFFUSION, 4 * 32, "tpu", _mesh(tp=2), True, "dense"),
        (DIFFUSION, 4 * 32, "cpu", None, True, "dense"),
        (DIFFUSION, 1024, "tpu", None, False, "grouped"),   # its prefill
    ],
)
def test_the_chooser_is_a_function_of_rows_platform_and_mesh(
    change, rows, platform, mesh, decode, want
):
    cfg = dataclasses.replace(QWEN3_MOE, **change)
    assert moe_dispatch(rows, cfg, platform, mesh, decode=decode) == want
    if not decode:      # the argument's default
        assert moe_dispatch(rows, cfg, platform, mesh) == want


@pytest.mark.parametrize("change,rows,want", [
    ({}, 1, True), ({}, 4, False), ({}, 0, False),
    (DIFFUSION, 4, True), (DIFFUSION, 1, True), (DIFFUSION, 8, False),
    (DIFFUSION, 2, False),
])
def test_a_step_over_the_cache_is_one_row_or_a_diffusion_block_s(
    change, rows, want
):
    """What ``forward`` tells the chooser of ``T`` rows a slot over a
    cache: a verify step's 4 rows are no step of a causal model's, a
    block pass's 4 are the diffusion model's own."""
    from gpustack_tpu.models.transformer import steps_over_cache

    cfg = dataclasses.replace(QWEN3_MOE, **change)
    assert steps_over_cache(cfg, rows) is want


def test_the_crossover_is_rows_an_expert_not_a_row_count():
    few = dataclasses.replace(BASE, num_experts=8, num_experts_per_tok=2)
    at = GROUPED_MIN_FILL * 8 // 2
    assert moe_dispatch(at, few, "tpu", None) == "grouped"
    assert moe_dispatch(at - 1, few, "tpu", None) == "dense"


def test_grouped_experts_find_their_layer_behind_leading_dense_layers():
    """``forward`` hands the kernel all the layers' expert weights and the
    layer's index among them: with dense layers in front (DeepSeek's
    ``first_k_dense``) that index starts after them. Shared experts ride
    along. Grouped logits against dense logits, float32."""
    cfg = dataclasses.replace(
        get_config("tiny-moe"), dtype="float32", num_layers=3,
        first_k_dense=1, n_shared_experts=1,
        shared_expert_intermediate_size=32,
    )
    params = init_params(cfg, jax.random.key(2))
    assert len(params["dense_layers"]["w_gate"]) == 1
    toks = jax.random.randint(jax.random.key(3), (1, 70), 0, cfg.vocab_size)
    pos = jnp.arange(70, dtype=jnp.int32)[None, :]
    dense, grouped = (
        jax.jit(lambda t, p: forward(
            params, cfg, t, p, moe_dispatch_impl=impl
        )[0])(toks, pos)
        for impl in ("dense", "grouped_interpret")
    )
    np.testing.assert_allclose(
        np.asarray(grouped), np.asarray(dense), rtol=2e-4, atol=2e-4
    )


def test_prefill_through_grouped_experts_agrees_with_decode_through_the_cache():
    """``perfbench/checks.py``'s check 3 in small: a prefill over P + 4
    tokens (grouped) against a prefill over P (dense) and four decode
    steps through the cache (dense), logits at the last four positions."""
    cfg = dataclasses.replace(get_config("tiny-moe"), dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    P, S = 140, 160
    toks = jax.random.randint(jax.random.key(1), (1, P + 4), 0, cfg.vocab_size)
    pos = jnp.arange(P + 4, dtype=jnp.int32)[None, :]

    whole, _ = jax.jit(lambda t, p: forward(
        params, cfg, t, p, KVCache.create(cfg, 1, S),
        moe_dispatch_impl="grouped_interpret",
    ))(toks, pos)
    _, cache = jax.jit(lambda t, p: forward(
        params, cfg, t, p, KVCache.create(cfg, 1, S),
    ))(toks[:, :P], pos[:, :P])
    step = jax.jit(lambda t, p, c: forward(params, cfg, t, p, c))
    for i in range(P, P + 4):
        logits, cache = step(toks[:, i:i + 1], pos[:, i:i + 1], cache)
        np.testing.assert_allclose(
            np.asarray(logits[0, 0]), np.asarray(whole[0, i]),
            rtol=2e-4, atol=2e-4,
        )


# ---- a decode step through the touched experts (PR 43) ----

DECODE_CONFIGS = {
    # Qwen3-MoE's router: 128 experts, 8 a token, softmax, renormalised
    "qwen3_moe": QWEN3_MOE,
    "shared": BASE,
    # A.X-K1 / DeepSeek-V3: sigmoid scores, a correction bias, 2.5 x
    "sigmoid_bias": SCORINGS["sigmoid_bias"],
    # one chip's share: experts 4 and 5 of the router's 8
    "share_of_2_from_4": dataclasses.replace(
        SCORINGS["sigmoid_bias"], experts_held=2, first_held_expert=4
    ),
    # Nemotron-H's two-matrix experts, whole and as a share
    "relu2": SCORINGS["relu2"],
    "relu2_share_of_2_from_4": dataclasses.replace(
        SCORINGS["relu2"], experts_held=2, first_held_expert=4
    ),
}


def _decode_layer(name: str, int8: bool, rows: int, seed: int = 0):
    """``rows`` slots of one token each through one layer of experts:
    ``_layer``'s tensors at the configuration's own expert counts, the
    rows as ``[rows, 1, D]``."""
    cfg = DECODE_CONFIGS[name]
    n_router, held = cfg.num_experts, cfg.num_held_experts
    ks = jax.random.split(jax.random.key(seed + len(name) + rows), 10)
    dtype = jnp.bfloat16 if int8 else jnp.float32
    x = jax.random.normal(ks[0], (rows, 1, D), jnp.float32).astype(dtype)
    router = (jax.random.normal(ks[1], (D, n_router)) * 0.3).astype(dtype)
    w = {
        "we_gate": jax.random.normal(ks[2], (held, D, F)) * 0.2,
        "we_up": jax.random.normal(ks[3], (held, D, F)) * 0.2,
        "we_down": jax.random.normal(ks[4], (held, F, D)) * 0.2,
    }
    if int8:
        w = quantize_params({"layers": {
            k: v[None] for k, v in w.items()
        }})["layers"]
        w = {k: QuantW(q=v.q[0], s=v.s[0]) for k, v in w.items()}
    kw = {}
    if cfg.moe_scoring == "sigmoid":
        kw["router_bias"] = jax.random.normal(ks[5], (n_router,)) * 0.05
    if name != "qwen3_moe":
        kw["shared"] = (
            (jax.random.normal(ks[6], (D, F)) * 0.2).astype(dtype),
            (jax.random.normal(ks[7], (D, F)) * 0.2).astype(dtype),
            (jax.random.normal(ks[8], (F, D)) * 0.2).astype(dtype),
            None,
        )
    w, kw = _plain(cfg, w, kw)
    return cfg, x, router, w, kw


def _decode_mlp(cfg, x, router, w, kw, dispatch, live=None, **more):
    return jax.jit(lambda x, live: _moe_mlp(
        x, router, w["we_gate"], w["we_up"], w["we_down"], cfg,
        dispatch=dispatch, live=live, **kw, **more,
    ))(x, live)


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("name", sorted(DECODE_CONFIGS))
def test_touched_equals_dense_pair_by_pair_and_counts_what_it_read(
    name, int8
):
    """Every live row's result is the dense form's and the per-pair
    reference's within the file's grouped-against-dense bounds; a dead
    row's routed part is zero (the shared expert is all it gets); the
    count is the distinct held experts the live rows chose."""
    rows = 32 if name == "qwen3_moe" else 12
    cfg, x, router, w, kw = _decode_layer(name, int8, rows)
    live = jnp.arange(rows) % 4 == 1          # a quarter of the slots
    dense = _decode_mlp(cfg, x, router, w, kw, "dense")
    touched, n_read = _decode_mlp(
        cfg, x, router, w, kw, "touched_interpret", live, count_read=True
    )
    top_idx, top_w = jax.jit(
        lambda x: _route(x, router, cfg, kw.get("router_bias"))
    )(x)
    first, held = cfg.first_held_expert, cfg.num_held_experts
    chosen = np.asarray(top_idx)[np.asarray(live)].ravel() - first
    distinct = set(chosen[(chosen >= 0) & (chosen < held)].tolist())
    assert int(n_read) == len(distinct)
    if name == "qwen3_moe":                   # 8 rows x 8: well under 128
        assert 30 < len(distinct) < 64
    ref = _reference(
        cfg, jnp.swapaxes(x, 0, 1), jnp.swapaxes(top_idx, 0, 1),
        jnp.swapaxes(top_w, 0, 1), w, kw, first,
    )
    top = np.abs(ref).max()
    bound = 2e-5 if not int8 else 2.0 ** -6
    mine, theirs = (
        np.asarray(a, np.float64)[:, 0] for a in (touched, dense)
    )
    at = np.asarray(live)
    assert np.abs(mine[at] - ref[at]).max() / top < bound
    assert np.abs(mine[at] - theirs[at]).max() / top < bound
    # a dead row: nothing routed
    alone = _decode_mlp(
        cfg, x, router, w, kw, "touched_interpret", jnp.zeros(rows, bool),
        count_read=True,
    )
    assert int(alone[1]) == 0 and np.isfinite(np.asarray(alone[0], np.float32)).all()
    np.testing.assert_array_equal(
        np.asarray(alone[0], np.float32)[~at],
        np.asarray(touched, np.float32)[~at],
    )
    if "shared" not in kw:
        assert not np.asarray(alone[0], np.float32).any()
    # no ``live``: every slot routes, and the whole batch is dense's
    every, n_all = _decode_mlp(
        cfg, x, router, w, kw, "touched_interpret", count_read=True
    )
    assert np.abs(
        np.asarray(every, np.float64)[:, 0] - theirs
    ).max() / top < bound
    assert int(n_all) >= int(n_read)
    # under dense every held expert is read, whatever was routed
    assert int(_decode_mlp(
        cfg, x, router, w, kw, "dense", live, count_read=True
    )[1]) == held


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_a_row_s_bits_are_its_own_whatever_its_neighbours_hold(int8):
    """Row 3 through the touched experts, three times: beside the rows
    it came with, beside other rows (which touch other experts, so the
    kernel walks another list), and with other slots live. Its result is
    the same bits: experts are added in id order, and one a row did not
    choose adds ``0 * y``."""
    cfg, x, router, w, kw = _decode_layer("sigmoid_bias", int8, 12)
    _, other, *_ = _decode_layer("sigmoid_bias", int8, 12, seed=7)
    others = other.at[3].set(x[3])
    live_a = jnp.arange(12) % 3 == 0
    live_b = jnp.arange(12) >= 3
    outs, reads = zip(*(
        _decode_mlp(
            cfg, rows, router, w, kw, "touched_interpret", live,
            count_read=True,
        )
        for rows, live in ((x, live_a), (others, live_a), (x, live_b))
    ))
    assert len({int(n) for n in reads}) > 1     # not the same walk
    mine = [np.asarray(o, np.float32)[3] for o in outs]
    np.testing.assert_array_equal(mine[0], mine[1])
    np.testing.assert_array_equal(mine[0], mine[2])


def test_the_intermediate_width_in_chunks_is_the_whole_width():
    """``touched_experts`` over ``F`` in two chunks (A.X-K1's experts
    are walked in eight) against one: the same sum in another order."""
    ks = jax.random.split(jax.random.key(5), 5)
    B, Dm, Fm, Eh = 6, 32, 256, 5
    x = jax.random.normal(ks[0], (B, Dm))
    gate, up = (jax.random.normal(k, (Eh, Dm, Fm)) * 0.2 for k in ks[1:3])
    down = jax.random.normal(ks[3], (Eh, Fm, Dm)) * 0.2
    combine = jnp.zeros((B, Eh)).at[0, 1].set(0.4).at[0, 4].set(0.6)
    combine = combine.at[2, 4].set(1.0).at[5, 0].set(0.25)
    ids = jnp.array([0, 1, 4, 4, 4], jnp.int32)
    n = jnp.array([3], jnp.int32)
    whole, halves = (
        gm.touched_experts(
            x, combine, ids, n, gate, up, down, interpret=True, _block_f=bf
        )
        for bf in (None, 128)
    )
    assert gm.choose_block_f(B, Dm, Fm, 4, 4) == Fm
    assert whole.dtype == jnp.float32 and whole.shape == (B, Dm)
    np.testing.assert_allclose(
        np.asarray(halves), np.asarray(whole), rtol=1e-5, atol=1e-5
    )
    assert not np.asarray(whole)[[1, 3, 4]].any()   # rows that chose none
    # the cells' widths: the MoE's expert whole, A.X-K1's in chunks of 256
    assert gm.choose_block_f(32, 2048, 768, 2, 1) == 768
    assert gm.choose_block_f(16, 7168, 2048, 2, 1) == 256
    # widths that do not halve: a divisor of F in whole lane tiles
    # (DeepSeek-V2-Lite's 1408 = 11 x 128 in bf16, Mixtral's 14336)
    assert gm.choose_block_f(32, 2048, 1408, 2, 2) == 128
    assert gm.choose_block_f(8, 4096, 14336, 2, 2) == 512
    assert gm.choose_block_f(4, 64, 96, 4, 4) == 96    # no lane tile: whole


def test_a_model_the_kernel_refuses_is_refused_aloud():
    cfg, x, router, w, kw = _layer("gptoss", "even", False, False, 8)
    with pytest.raises(ValueError, match="touched"):
        _moe_mlp(
            jnp.swapaxes(x, 0, 1), router, w["we_gate"], w["we_up"],
            w["we_down"], cfg, dispatch="touched_interpret", **kw,
        )


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8-bf16"])
@pytest.mark.parametrize("hf_share", [None, (4, 4)], ids=["all", "share"])
def test_a_decode_step_of_the_axk1_family_through_touched_experts(
    hf_share, int8
):
    """A.X-K1's tiny preset (a leading dense layer, a shared expert,
    sigmoid scores in kept groups, 2.5 x, and a share of 4 from id 4 on)
    one decode step over a cache, ``touched`` against ``dense``: the
    live slots' logits agree, the cache is the same, ``routing_out``
    returns what it returned, and the count is the live rows' distinct
    held experts over both layers with experts."""
    from tests.models.test_axk1 import HF, model, share

    cfg, params = model(
        share(*hf_share) if hf_share else HF, int8=int8,
        dtype="bfloat16" if int8 else "float32",
    )
    slots, S = 6, 16
    toks = jax.random.randint(jax.random.key(2), (slots, 1), 0, cfg.vocab_size)
    pos = jnp.arange(slots, dtype=jnp.int32)[:, None]
    live = jnp.array([True, False, True, True, False, False])

    def step(impl):
        return jax.jit(lambda t, p, c: forward(
            params, cfg, t, p, c, moe_dispatch_impl=impl, live=live,
            routing_out=True, count_experts_read=True,
        ))(toks, pos, KVCache.create(cfg, slots, S))

    d_logits, d_cache, d_read, (d_chosen, d_scores) = step("dense")
    t_logits, t_cache, t_read, (t_chosen, t_scores) = step(
        "touched_interpret"
    )
    at = np.asarray(live)
    tol = dict(rtol=2e-4, atol=2e-4) if not int8 else dict(rtol=0, atol=0.06)
    np.testing.assert_allclose(
        np.asarray(t_logits)[at], np.asarray(d_logits)[at], **tol
    )
    # a layer's cache rows come from the layers below, so a live slot's
    # rows agree as its logits do; the routing is what the program chose
    np.testing.assert_allclose(
        np.asarray(t_cache.k, np.float32)[:, at],
        np.asarray(d_cache.k, np.float32)[:, at], **tol
    )
    assert t_chosen.shape == d_chosen.shape == (2, slots, 1, 4)
    np.testing.assert_array_equal(
        np.asarray(t_chosen)[0], np.asarray(d_chosen)[0]
    )
    assert t_scores.shape == (2, slots, 1, 16)
    first, held = cfg.first_held_expert, cfg.num_held_experts
    want = 0
    for layer in range(2):
        ids = np.asarray(t_chosen)[layer][at].ravel() - first
        want += len(set(ids[(ids >= 0) & (ids < held)].tolist()))
    assert int(t_read) == want and int(d_read) == 2 * held


def test_prefill_through_grouped_experts_agrees_with_decode_through_touched():
    """The sibling of the test above with the decode steps as one TPU
    chip runs them since PR 43: prefill over P + 4 tokens (grouped,
    float32 combine weights) against four decode steps through the
    cache and the touched experts (float32 combine weights too), with a
    dead slot beside the live one."""
    cfg = dataclasses.replace(get_config("tiny-moe"), dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    P, S = 140, 160
    toks = jax.random.randint(jax.random.key(1), (1, P + 4), 0, cfg.vocab_size)
    pos = jnp.arange(P + 4, dtype=jnp.int32)[None, :]

    whole, _ = jax.jit(lambda t, p: forward(
        params, cfg, t, p, KVCache.create(cfg, 1, S),
        moe_dispatch_impl="grouped_interpret",
    ))(toks, pos)
    _, one = jax.jit(lambda t, p: forward(
        params, cfg, t, p, KVCache.create(cfg, 1, S),
    ))(toks[:, :P], pos[:, :P])
    # two slots: the prompt's, and one nobody holds
    cache = jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a, jnp.zeros_like(a)], axis=1), one
    )
    live = jnp.array([True, False])
    step = jax.jit(lambda t, p, c: forward(
        params, cfg, t, p, c, moe_dispatch_impl="touched_interpret",
        live=live, count_experts_read=True,
    ))
    for i in range(P, P + 4):
        logits, cache, n_read = step(
            jnp.tile(toks[:, i:i + 1], (2, 1)),
            jnp.tile(pos[:, i:i + 1], (2, 1)), cache,
        )
        np.testing.assert_allclose(
            np.asarray(logits[0, 0]), np.asarray(whole[0, i]),
            rtol=2e-4, atol=2e-4,
        )
        # one live row, two experts a layer, two layers
        assert int(n_read) == cfg.num_layers * cfg.num_experts_per_tok
