"""Transformer LM: forward/decode equivalence, sharded training step."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from client_tpu.parallel import (
    batch_spec,
    make_mesh,
    named_shardings,
    param_specs,
)
from client_tpu.serve.models import transformer as tfm

CFG = tfm.TransformerConfig(
    vocab_size=128,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    max_seq=32,
    dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(0), CFG)


def test_forward_shape_and_finite(params):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, CFG.vocab_size)
    logits = tfm.forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_prefill_decode_matches_forward(params):
    """Incremental decoding must reproduce the full-sequence logits."""
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0, CFG.vocab_size)
    full = np.asarray(tfm.forward(params, tokens, CFG))

    cache = tfm.init_cache(CFG, 1)
    prefix = tokens[:, :8]
    logits, cache = tfm.prefill(params, prefix, CFG, cache)
    np.testing.assert_allclose(np.asarray(logits), full[:, 7], atol=2e-4, rtol=1e-3)
    for i in range(8, 12):
        logits, cache = tfm.decode_step(params, tokens[:, i], CFG, cache)
        np.testing.assert_allclose(
            np.asarray(logits), full[:, i], atol=2e-4, rtol=1e-3
        )


def test_ring_forward_matches_plain(params):
    mesh = make_mesh(dp=2, tp=2, sp=2)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, CFG.vocab_size)
    plain = np.asarray(tfm.forward(params, tokens, CFG))
    sharded_params = jax.device_put(params, named_shardings(mesh, param_specs(CFG)))
    sharded_tokens = jax.device_put(
        tokens, jax.sharding.NamedSharding(mesh, batch_spec())
    )
    ring = np.asarray(
        tfm.forward(sharded_params, sharded_tokens, CFG, mesh=mesh, attn_impl="ring")
    )
    np.testing.assert_allclose(ring, plain, atol=1e-4, rtol=1e-3)


def test_train_step_reduces_loss(params):
    opt, step = tfm.make_train_step(CFG, learning_rate=1e-2)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 17), 0, CFG.vocab_size)
    p = jax.tree.map(jnp.copy, params)  # step donates its inputs
    first = None
    for _ in range(5):
        p, opt_state, loss = step(p, opt_state, tokens)
        if first is None:
            first = float(loss)
    assert float(loss) < first


def test_sharded_train_step_runs():
    """dp/tp/sp train step on the 8-device mesh — the dryrun_multichip path."""
    mesh = make_mesh(dp=2, tp=2, sp=2)
    params = tfm.init_params(jax.random.PRNGKey(5), CFG)
    opt, step = tfm.make_train_step(CFG, mesh=mesh, attn_impl="ring")
    shardings = named_shardings(mesh, param_specs(CFG))
    params = jax.device_put(params, shardings)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (4, 17), 0, CFG.vocab_size)
    # seq len 17: forward sees 16 tokens (sp-divisible), targets get 16
    tokens = jax.device_put(
        tokens, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("dp", None))
    )
    params, opt_state, loss = step(params, opt_state, tokens)
    assert np.isfinite(float(loss))


def test_generate_streams_tokens(params):
    toks = list(
        tfm.generate(params, CFG, prompt=[1, 2, 3], max_new_tokens=4)
    )
    assert len(toks) == 4
    assert all(0 <= t < CFG.vocab_size for t in toks)


def test_generate_pipelined_matches_serial(params):
    """Deferring the D2H readback must not change the token stream."""
    serial = list(
        tfm.generate(params, CFG, prompt=[5, 9], max_new_tokens=12,
                     readback_depth=0)
    )
    for depth in (1, 4, 32):
        pipelined = list(
            tfm.generate(params, CFG, prompt=[5, 9], max_new_tokens=12,
                         readback_depth=depth)
        )
        assert pipelined == serial


def test_generate_pipelined_matches_serial_sampled(params):
    """Sampling path: the key-split schedule is per-step, so the stream is
    depth-invariant there too."""
    kw = dict(prompt=[3, 4, 5], max_new_tokens=10, temperature=0.7,
              key=jax.random.PRNGKey(7))
    serial = list(tfm.generate(params, CFG, readback_depth=0, **kw))
    pipelined = list(tfm.generate(params, CFG, readback_depth=8, **kw))
    assert pipelined == serial


# -- the serving layout -------------------------------------------------------

def _served(params):
    """``params`` in the serving layout, the given tree left as it was
    (``serving_params`` takes over the tree it is handed)."""
    return tfm.serving_params(jax.tree_util.tree_map(lambda a: a, params))


def _paged_run(params, block=8):
    """Two prompts prefilled into zeroed pools, then four sampled decode
    ticks of both lanes, each program jitted as the engine jits it: every
    token and the pools it leaves."""
    chunk_fn = jax.jit(functools.partial(
        tfm.paged_prefill_chunk, cfg=CFG, block_size=block))
    tick_fn = jax.jit(functools.partial(
        tfm.paged_decode_tick, cfg=CFG, n=2, block_size=block))
    width = CFG.max_seq // block
    shape = (2 * width + 1, CFG.n_kv_heads, block, CFG.head_dim)
    pool_k = [jnp.zeros(shape) for _ in range(CFG.n_layers)]
    pool_v = list(pool_k)
    tables = np.arange(1, 2 * width + 1, dtype=np.int32).reshape(2, width)
    prompts = [[5, 9, 3, 77, 12, 40, 8, 1, 2, 90, 33], [7, 7, 100, 4, 61]]
    tokens = []
    for lane, prompt in enumerate(prompts):
        chunk = np.zeros((1, 16), np.int32)
        chunk[0, :len(prompt)] = prompt
        tok, pool_k, pool_v, _ = chunk_fn(
            params, chunk, pool_k, pool_v, tables[lane], np.int32(0),
            np.int32(len(prompt)), jax.random.PRNGKey(lane), np.float32(0),
            np.int32(0))
        tokens.append(int(tok))
    lens = np.array([len(p) for p in prompts], np.int32)
    pending, keys, out = jnp.array(tokens, jnp.int32), jax.random.split(
        jax.random.PRNGKey(3), 2), [tokens]
    for _ in range(4):
        pending, pool_k, pool_v, keys = tick_fn(
            params, pending, pool_k, pool_v, tables, lens,
            np.ones(2, bool), np.full(2, 0.8, np.float32),
            np.zeros(2, np.int32), keys)
        out.append(np.asarray(pending).tolist())
        lens = lens + 1
    return out, [np.asarray(p) for p in pool_k + pool_v]


def _assert_same_run(got, want):
    """Two ``_paged_run`` results: the same tokens, and the same pools to
    float32 rounding."""
    assert got[0] == want[0]
    for pool, expected in zip(got[1], want[1]):
        np.testing.assert_allclose(pool, expected, rtol=1e-5, atol=1e-5)


def test_the_serving_layout_serves_the_tokens_the_published_layout_does(
        params):
    """``serving_params`` holds wq, wk and wv as [out, in] under keys that
    say so, and leaves every other weight as it was; the chunk, the tick
    (sampled) and the serial ``generate`` give the same tokens from either
    form, and the same pools to float32 rounding: the CPU's product sums
    a contraction over the other operand layout in another order."""
    served = _served(params)
    assert "wq" in params["layers"][0]["attn"]  # the tree given is kept
    for layer, was in zip(served["layers"], params["layers"]):
        assert sorted(layer["attn"]) == ["wk_t", "wo", "wq_t", "wv_t"]
        for name in ("wq", "wk", "wv"):
            np.testing.assert_array_equal(
                np.asarray(layer["attn"][name + "_t"]),
                np.asarray(was["attn"][name]).T)
        assert layer["attn"]["wo"] is was["attn"]["wo"]
        assert all(layer["mlp"][k] is w for k, w in was["mlp"].items())
    _assert_same_run(_paged_run(served), _paged_run(params))
    kw = dict(prompt=[5, 9, 3], max_new_tokens=8)
    assert list(tfm.generate(served, CFG, **kw)) == \
        list(tfm.generate(params, CFG, **kw))


def test_an_int8_layer_passes_the_serving_layout_untouched(params):
    """An int8 pair is read by its own kernel as [in, out]: the transform
    leaves it, a float layer beside it is laid out, and the mixed tree
    serves what it served before.  Quantizing params already in the
    serving layout gives what quantizing the published ones gives (the
    stock language set quantizes the shared runner's params)."""
    quantized = tfm.quantize_params(params)
    mixed = dict(params, layers=[quantized["layers"][0], params["layers"][1]])
    served = _served(mixed)
    assert served["layers"][0]["attn"] == quantized["layers"][0]["attn"]
    assert sorted(served["layers"][1]["attn"]) == [
        "wk_t", "wo", "wq_t", "wv_t"]
    got, want = _paged_run(served), _paged_run(mixed)
    _assert_same_run(got, want)
    for layer_0 in (0, CFG.n_layers):  # its k and v: the same products
        np.testing.assert_array_equal(got[1][layer_0], want[1][layer_0])
    again = tfm.quantize_params(_served(params))
    for got, want in zip(jax.tree_util.tree_leaves(again),
                         jax.tree_util.tree_leaves(quantized)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert jax.tree_util.tree_structure(again) == \
        jax.tree_util.tree_structure(quantized)


def test_the_runner_lays_the_params_out_once_and_the_engine_gets_them(
        params, monkeypatch):
    """``_LmRunner`` applies the family's ``serving_params`` once, after
    the int8 transform where there is one, and the batched model's engine
    serves the very params the runner holds."""
    from client_tpu.serve.models.language import (
        _LmRunner, lm_streaming_batched_model)

    calls = []
    real = tfm.serving_params

    def spy(tree):
        calls.append(tree)
        return real(tree)

    monkeypatch.setattr(tfm.DecoderPrograms, "serving_params",
                        staticmethod(spy))
    runner = _LmRunner(cfg=CFG, params=jax.tree_util.tree_map(
        lambda a: a, params))
    assert len(calls) == 1
    assert sorted(runner.params["layers"][0]["attn"]) == [
        "wk_t", "wo", "wq_t", "wv_t"]
    int8 = _LmRunner(cfg=CFG, params=runner.params, quantize=True)
    assert len(calls) == 2
    assert sorted(int8.params["layers"][0]["attn"]) == [
        "wk", "wo", "wq", "wv"]
    model = lm_streaming_batched_model(
        runner=runner, max_slots=2, lane_counts=(2,), block_size=8,
        prefill_chunk=16, min_bucket=4, prefix_cache=False)
    try:
        assert model.runner.scheduler.params is runner.params
        streamed = list(model.runner.stream([5, 9, 3], 6))
    finally:
        model.closer()
    assert len(calls) == 2
    assert streamed == list(tfm.generate(params, CFG, prompt=[5, 9, 3],
                                         max_new_tokens=6))
