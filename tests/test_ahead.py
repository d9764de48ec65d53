"""How far ``LmEngine``'s scheduler runs ahead of the device
(``LmEngine._drain_ahead``): it reads back while the priced device time
queued behind the oldest result is more than its cover, under the cap of
``readback_depth``.  Tokens do not depend on it; where a first chunk
starts, ``ahead_s`` and ``inflight`` in ``tick_trace()`` and the
``ctpu_lm_ahead_*`` series do.

The estimate is stubbed where a test needs it fixed: every program's
device time a set number of seconds (what completes is not recorded),
and the host's pass a set number of seconds."""

import collections
import functools

import pytest

import jax
import jax.numpy as jnp

from benchmark import weights_sdar
from client_tpu.serve.lm import LmEngine
from client_tpu.serve.lm import engine as engine_mod
from client_tpu.serve.metrics import Registry
from client_tpu.serve.models import sdar
from client_tpu.serve.models import transformer as tfm

CLOSE = LmEngine.CLOSE

DECODER = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq=96, dtype="float32")

SDAR_CONFIG = {
    "hidden_size": 32, "moe_intermediate_size": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 97,
    "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "assumed": {"block_length": 4, "denoising_steps": 4, "mask_token_id": 96},
}
SDAR = sdar.SdarConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    head_dim=16, d_ff=16, n_experts=8, top_k=2, experts_held=tuple(range(8)),
    block_length=4, denoising_steps=4, mask_id=96, max_seq=96,
    dtype="float32")

TICK_S, CHUNK_S = 0.010, 0.020  # the stubbed device times
HOST_S = 0.004                  # a stubbed pass of the host's


@functools.lru_cache(maxsize=None)
def _params(family):
    if family == "decoder":
        return tfm.init_params(jax.random.PRNGKey(0), DECODER)
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        weights_sdar.sdar_params(SDAR_CONFIG, 11))


def _engine(family="decoder", **kwargs):
    args = dict(max_slots=4, lane_counts=(4,), block_size=4 if family ==
                "sdar" else 8, prefill_chunk=16, min_bucket=4)
    args.update(kwargs)
    cfg = DECODER if family == "decoder" else SDAR
    return LmEngine(_params(family), cfg, **args)


class _Prices(dict):
    """The engine's device-time estimate, fixed: a decode tick takes
    ``TICK_S``, a chunk ``CHUNK_S``, and a completion adds nothing."""

    def get(self, key, default=None):
        seconds = TICK_S if key[0] == "decode" else CHUNK_S
        return collections.deque([seconds] * engine_mod.AHEAD_PRICE_NEEDS)


class _HostTimes(collections.deque):
    """The host's recent passes, fixed: a pass adds nothing."""

    def append(self, _seconds):
        pass


def _stub(eng, host_s=HOST_S):
    """Fix the estimate; returns the cover the rule then keeps."""
    eng._device_times = _Prices()
    eng._host_s = _HostTimes([host_s] * engine_mod.AHEAD_COVER_NEEDS)
    return engine_mod.AHEAD_MARGIN * host_s


def _collect(q, timeout=120):
    out = []
    while True:
        token = q.get(timeout=timeout)
        if token is CLOSE:
            return out
        out.append(token)


def _streams(eng, prompts):
    queues = [eng.submit(prompt, n)[0] for prompt, n in prompts]
    return [_collect(q) for q in queues]


PROMPTS = [([3] * 5, 30), ([5, 6, 7] * 7, 22), ([9] * 40, 17)]


@pytest.mark.parametrize("family", ["decoder", "sdar"])
@pytest.mark.parametrize("estimate", ["stubbed", "observed"])
def test_the_tokens_are_those_of_a_serial_engine(family, estimate):
    """Only when results are read back changes: every stream's tokens are
    those of an engine that reads back everything every pass.  With the
    estimate stubbed the rule is sure to engage; observed, it engages as
    the host's and device's times fall."""
    serial = _engine(family, readback_depth=0)
    try:
        want = _streams(serial, PROMPTS)
    finally:
        serial.close()
    reg = Registry()
    eng = _engine(family, registry=reg)
    if estimate == "stubbed":
        _stub(eng)
    try:
        got = _streams(eng, PROMPTS)
    finally:
        eng.close()
    assert got == want
    assert [len(t) for t in got] == [n for _, n in PROMPTS]
    if estimate == "stubbed":
        assert reg.get("ctpu_lm_ahead_drains_total") > 0


def _second_first_chunk(host_s, **kwargs):
    """(the first chunk of a request submitted to a decoding engine, the
    cover) under a stubbed estimate."""
    eng = _engine(**kwargs)
    cover = _stub(eng, host_s)
    try:
        q, _ = eng.submit([3] * 5, 60)
        for _ in range(12):  # decoding: a dozen tokens read back
            assert q.get(timeout=120) is not CLOSE
        q2, _ = eng.submit([4] * 5, 4)
        assert len(_collect(q2)) == 4
        assert len(_collect(q)) == 48
    finally:
        eng.close()
    firsts = sorted((t for t in eng.tick_trace() if "t_submit" in t),
                    key=lambda t: t["t0"])
    assert [t["kind"] for t in firsts] == ["prefill_chunk"] * 2
    return firsts[1], cover


def test_a_first_chunk_waits_behind_the_cover_and_one_tick():
    chunk, cover = _second_first_chunk(HOST_S)
    assert 0 < chunk["ahead_s"] <= cover + TICK_S


def test_under_a_cover_of_seconds_a_first_chunk_waits_behind_the_cap():
    """A host that needs more than the cap's device time: the rule never
    reads back below ``readback_depth``, so a first chunk waits behind
    the cap's eight ticks (the default)."""
    chunk, _ = _second_first_chunk(10.0)
    assert chunk["ahead_s"] == pytest.approx(8 * TICK_S)


@pytest.mark.parametrize("depth", [0, 3])
def test_readback_depth_caps_the_results_in_flight(depth):
    eng = _engine(readback_depth=depth)
    _stub(eng, 10.0)
    try:
        got = _streams(eng, PROMPTS)
    finally:
        eng.close()
    ticks = [t for t in eng.tick_trace() if t["kind"] == "decode"]
    assert max(t["inflight"] for t in ticks) == depth
    assert [len(t) for t in got] == [n for _, n in PROMPTS]


@pytest.mark.parametrize("host_s, drains", [(HOST_S, True), (10.0, False)],
                         ids=["the-rule-reads-back", "the-cap-alone"])
def test_drains_are_counted_and_every_tick_says_what_it_left(host_s, drains):
    reg = Registry()
    eng = _engine(registry=reg)
    cover = _stub(eng, host_s)
    try:
        _streams(eng, PROMPTS)
    finally:
        eng.close()
    ticks = [t for t in eng.tick_trace() if t["kind"] == "decode"]
    assert ticks and all(0 <= t["inflight"] <= 8 for t in ticks)
    counted = reg.get("ctpu_lm_ahead_drains_total") or 0
    assert (counted > 0) is drains
    # what stays queued after a pass: at most the cover and one tick's
    # worth beyond it where the rule reads back
    queued = reg.get("ctpu_lm_ahead_seconds")
    assert queued is not None and queued >= 0
    if drains:
        assert max(t["inflight"] for t in ticks) <= 2
        assert queued <= cover + TICK_S + CHUNK_S
