"""The stream mixers' Pallas kernels (`ops/pallas_hyper_connections.py`,
ISSUE 58), interpreted on the CPU, against the `jnp` forms of
`ops/hyper_connections.py` they stand for: a half's read (`mix` + `pre`) and
its write back (`post`), by token count and Sinkhorn steps; rows past a
chunk's length; the rule that hands a trace one form or the other and the
note it leaves.  (The CPU backend refuses the model's own bf16 products, so
a whole bf16 prefill is the chip's: `scripts/time_prefill_steps.py`.)

On bit equality.  `u` and X' are sums of products that the CPU backend may
contract into fused multiply-adds wherever it sees a multiply and an add in
one fusion (the interpreted kernel is one program; the eager `jnp` form is
one program an op), and a TPU's vector unit has none.  So equality is
asserted on operands whose products are EXACT in float32, where a fused and
an unfused sum round alike: streams that are signed powers of two (`u`),
weights of eight bits of mantissa (X').  Over normal draws the two forms may
then differ in the last bf16 bit of a few values in ten thousand on this
backend, and that is what the second assertion allows; what the chip gives
is `scripts/time_hyper_connections.py`'s `differ`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dynamo_tpu.analysis import xla_ledger
from dynamo_tpu.models import ModelConfig, llama
from dynamo_tpu.ops import hyper_connections as hc
from dynamo_tpu.ops import pallas_hyper_connections as pallas_hc
from dynamo_tpu.ops import pallas_moe

import test_xing4_0 as xing

N, H = 4, 256
M = N * N + 2 * N
HOW = dict(eps=1e-6, clamp=(-30., 30.), rms_eps=1e-6)
# a step's rows: one chunk, the shared step's four, a count of no whole tile
TOKENS = [(16,), (64,), (4, 64), (512,), (200,)]
ULPS = 8 * np.finfo(np.float32).eps


def mixer(seed=0):
    """(phi [n, h, M], scale [3], base [M]) at unit scale: logits with a
    spread near 1, so that R is far from uniform."""
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(k[0], (N, H, M), jnp.float32) * (N * H) ** -.5,
            jnp.ones((3,), jnp.float32),
            jax.random.normal(k[1], (M,), jnp.float32) * 0.3)


def streams(lead, seed=1, powers=False):
    """bf16 [*lead, n, h]: normal draws, or signed powers of two (a product
    with any float32 weight is then exact)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(k[0], (*lead, N, H), jnp.float32)
    if powers:
        x = jnp.sign(x) * 2.0 ** jax.random.randint(
            k[1], x.shape, -3, 3).astype(jnp.float32)
    return x.astype(jnp.bfloat16)


def flat(x, lead):
    return x.reshape(int(np.prod(lead)), -1)


def read(x, mix_of, iters):
    return pallas_hc.read(x, *mix_of, iters=iters, interpret=True, **HOW)


def close(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.abs(got - want).max() <= ULPS * max(1.0, np.abs(want).max())


def last_bit_only(got, want):
    """bf16 arrays equal but for the last bit of under 1 value in 1000."""
    g, w = (np.asarray(a, np.float32) for a in (got, want))
    off = g != w
    return off.mean() < 1e-3 and (
        np.abs(g - w)[off] <= 2.0 ** -7 * np.abs(w)[off]).all()


@pytest.mark.parametrize("iters", [1, 20])
@pytest.mark.parametrize("lead", TOKENS, ids=lambda t: "x".join(map(str, t)))
def test_the_read_is_mix_and_pre(lead, iters):
    """One kernel a half: the weights `mix` gives within a few float32 ulps
    (the mean square and the product over n x h values are summed in another
    order), the normalised logits beside them, and `u` = `pre` of ITS
    weights bit for bit."""
    mix_of = mixer()
    pre, post, res, err, logits = pallas_hc.columns(N)
    for powers in (True, False):
        x = streams(lead, powers=powers)
        T = int(np.prod(lead))
        u, w = read(flat(x, lead), mix_of, iters)
        assert u.shape == (T, H) and u.dtype == jnp.bfloat16
        assert w.shape == (T, 128) and w.dtype == jnp.float32
        want = hc.mix(x.reshape(T, N, H), *mix_of, iters=iters, **HOW)
        assert close(w[:, pre], want.pre)
        assert close(w[:, post], want.post)
        assert close(w[:, res].reshape(T, N, N), want.res)
        assert close(w[:, err], want.err)
        assert close(w[:, logits].T,
                     hc._mix_logits(x.reshape(T, N, H), mix_of[0], 1e-6))
        if iters == 20:  # driven to doubly stochastic
            assert float(w[:, err].max()) < 1e-3
        mine = hc.pre(x.reshape(T, N, H), w[:, pre])
        if powers:
            assert jnp.array_equal(u, mine)
        else:
            assert last_bit_only(u, mine)


@pytest.mark.parametrize("lead", TOKENS, ids=lambda t: "x".join(map(str, t)))
def test_the_write_is_post(lead):
    """X'_k = post_k y + sum_j R[j, k] X_j in `post`'s order, rounded once:
    bit for bit given the same weights."""
    T = int(np.prod(lead))
    x = streams(lead)
    y = streams(lead, seed=2)[..., 0, :]
    want = hc.mix(x.reshape(T, N, H), *mixer(), iters=20, **HOW)
    _, post, res, _, _ = pallas_hc.columns(N)
    for exact in (True, False):
        wp, wr = want.post, want.res
        if exact:  # eight bits of mantissa: every product is exact
            wp, wr = (a.astype(jnp.bfloat16).astype(jnp.float32)
                      for a in (wp, wr))
        w = jnp.zeros((T, 128), jnp.float32).at[:, post].set(wp).at[
            :, res].set(wr.reshape(T, -1))
        got = pallas_hc.write(flat(x, lead), flat(y, lead), w, n=N,
                              interpret=True)
        ref = hc.post(x.reshape(T, N, H), y.reshape(T, H), wp, wr)
        assert got.shape == (T, N * H) and got.dtype == jnp.bfloat16
        if exact:
            assert jnp.array_equal(got.reshape(T, N, H), ref)
        else:
            assert last_bit_only(got.reshape(T, N, H), ref)


@pytest.mark.parametrize("tokens,valid", [(200, 137), (64, 1), (512, 384)])
def test_rows_past_a_chunks_length_change_no_other_row(tokens, valid):
    """A step's pad rows hold whatever the embedding of token 0 and the
    layers made of them: infinities and NaNs there leave every row below
    the chunk's length as it was, in both kernels."""
    mix_of = mixer()
    x = flat(streams((tokens,)), (tokens,))
    y = streams((tokens,), seed=2)[..., 0, :]
    bad = jnp.where(jnp.arange(N * H) % 3 == 0, jnp.inf, jnp.nan
                    ).astype(jnp.bfloat16)
    x_bad = x.at[valid:].set(bad)
    y_bad = y.at[valid:].set(bad[:H])
    u, w = read(x, mix_of, 20)
    u_bad, w_bad = read(x_bad, mix_of, 20)
    assert jnp.array_equal(u[:valid], u_bad[:valid])
    assert jnp.array_equal(w[:valid], w_bad[:valid])
    assert np.isfinite(np.asarray(w[:valid])).all()
    out = pallas_hc.write(x, y, w, n=N, interpret=True)
    out_bad = pallas_hc.write(x_bad, y_bad, w_bad, n=N, interpret=True)
    assert jnp.array_equal(out[:valid], out_bad[:valid])


# -- which form a trace takes ---------------------------------------------------- #

def test_the_rule_by_what_the_trace_observes(monkeypatch):
    """The kernels on a single-device TPU trace (compiled) and under a
    check (as it says), for bf16 streams of a hidden size in whole lanes and
    16 tokens or more; the `jnp` forms on the CPU, under a mesh, for
    float32 streams, a hidden size of no whole lanes and fewer tokens."""
    bf = jnp.bfloat16
    x = jnp.zeros((64, N * H), bf)

    def form(x):
        return pallas_hc.lowering(x, N)[0]

    def why(x):
        return pallas_hc.lowering(x, N)[1]

    assert form(x) is None and why(x) == "no single-device TPU trace"
    with pallas_moe.checked(interpret=True):
        assert form(x) is True and why(x) == "bf16 streams on one TPU device"
        assert form(jnp.zeros((4, 16, N * H), bf)) is True
        assert form(jnp.zeros((16, N * H), bf)) is True
        assert form(jnp.zeros((15, N * H), bf)) is None
        assert form(jnp.zeros((8, 1, N * H), bf)) is None
        assert form(x.astype(jnp.float32)) is None
        assert form(jnp.zeros((64, N * 64), bf)) is None
        assert pallas_hc.lowering(jnp.zeros((64, 8 * H), bf), 8)[0] is None
        assert why(x.astype(jnp.float32)) == "float32 streams"
        assert "16" in why(jnp.zeros((15, N * H), bf))
        assert "64" in why(jnp.zeros((64, N * 64), bf))
    with pallas_moe.checked(interpret=False):
        assert form(x) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []
    jax.make_jaxpr(lambda x: seen.append(form(x)) or x)(x)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    jax.make_jaxpr(jax.shard_map(
        lambda x: seen.append(form(x)) or x, mesh=mesh,
        in_specs=P(None, "tp"), out_specs=P(None, "tp")))(x)
    assert seen == [False, None]  # one device's trace; a `shard_map`'s


def half_of(lead, dtype=jnp.bfloat16):
    """(cfg, a layer's mixer params, streams [*lead, n x h], the half)."""
    cfg = ModelConfig.from_hf_config(dict(xing.TINY, hidden_size=H))
    phi, scale, base = mixer()
    lp = {"hc_mlp_phi": phi, "hc_mlp_scale": scale, "hc_mlp_base": base}
    x = streams(lead).astype(dtype).reshape(*lead, N * H)
    return cfg, lp, x, lambda u: (u * 0.5, "aux")


@pytest.mark.parametrize("lead", [(4, 64), (1, 40)],
                         ids=lambda t: "x".join(map(str, t)))
def test_the_residual_takes_the_kernels_and_notes_it(lead, monkeypatch):
    """`_residual` under a check: ONE `read` and ONE `write` a half over the
    step's rows as one [tokens, n x h] block, made inside the `hc.mix` /
    `hc.post` scopes; the streams, the half's other results and `err` come
    back in the carry's shapes, within a last bf16 bit of the `jnp` forms'
    (whose weights differ in a last float32 bit); the trace's note says
    which form and why."""
    cfg, lp, x, f = half_of(lead)
    T = int(np.prod(lead))
    calls = []
    for name in ("read", "write"):
        real = getattr(pallas_hc, name)
        monkeypatch.setattr(pallas_hc, name, lambda *a, _r=real, _n=name,
                            **kw: calls.append((_n, a[0].shape)) or _r(*a, **kw))
    want, aux0, err0 = llama._residual(cfg, lp, "hc_mlp", x, f)
    assert calls == [] and llama.hc_mixers(T) == "xla"
    with pallas_moe.checked(interpret=True):
        got, aux, err = llama._residual(cfg, lp, "hc_mlp", x, f)
        half = lambda x: llama._residual(cfg, lp, "hc_mlp", x, f)[0]  # noqa: E731
        calls_in = str(jax.make_jaxpr(half)(x)).count("pallas_call")
        text = jax.jit(half).lower(x).as_text(debug_info=True)
    assert calls[:2] == [("read", (T, N * H)), ("write", (T, N * H))]
    assert llama.hc_mixers(T) == "kernel"
    assert got.shape == x.shape and got.dtype == x.dtype
    assert aux == aux0 == ["aux"] and err.shape == err0.shape == lead
    assert close(err, err0)
    g, w = (np.asarray(a, np.float32) for a in (got, want))
    off = g != w  # a last bit of `u`, handed on through the half
    assert off.mean() < 1e-3
    assert (np.abs(g - w)[off] <= 2.0 ** -6 * np.abs(w)[off]).all()
    assert calls_in == 2 and "hc.mix/" in text and "hc.post/" in text
    notes = [c for c in xla_ledger.summary()["path_choices"]
             if c["site"] == "hc_mixers" and c["dims"] == f"tokens={T}"]
    assert {(c["choice"], c["reason"]) for c in notes} == {
        ("kernel", "bf16 streams on one TPU device"),
        ("xla", "no single-device TPU trace")}


def test_what_the_kernels_do_not_take_keeps_the_jnp_forms(monkeypatch):
    """Under a check all the same: float32 streams and a decode step's few
    rows."""
    monkeypatch.setattr(pallas_hc, "read", None)  # would raise
    with pallas_moe.checked(interpret=True):
        for lead, dtype, why in (((4, 64), jnp.float32, "float32 streams"),
                                 ((8, 1), jnp.bfloat16,
                                  "fewer than 16 tokens")):
            cfg, lp, x, f = half_of(lead, dtype)
            out, _, err = llama._residual(cfg, lp, "hc_mlp", x, f)
            T = int(np.prod(lead))
            assert out.shape == x.shape and err.shape == lead
            assert llama.hc_mixers(T) == "xla"
            assert any(c["reason"] == why and c["dims"] == f"tokens={T}"
                       for c in xla_ledger.summary()["path_choices"]
                       if c["site"] == "hc_mixers")
