"""A prefill step runs the output head only where a row samples (ISSUE 41):
`forward_prefill` takes which rows sample and puts everything after the
layer loop that exists only to produce a token under one conditional; the
engine hands it `PrefillItem.samples`, says `head` on the step's slice and
counts the steps that went without.  The branch that runs the head is the
parent's code on the parent's operands: every comparison here is bit for
bit.  Tiny sizes, float32, seeded weights, CPU."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.layout import Layout
from dynamo_tpu.models import KVCache, ModelConfig, init_params, tiny_config
from dynamo_tpu.models import llama
from dynamo_tpu.ops.sampling import SamplingParams

PAGE = 8


def model_of(family):
    if family == "dense":
        return tiny_config()
    import test_deepseek_v3
    import test_smallthinker
    import test_xing4_0

    tiny = {"smallthinker": test_smallthinker.TINY,
            "deepseek_v3": test_deepseek_v3.TINY,
            "xing4_0": test_xing4_0.TINY}[family]
    return ModelConfig.from_hf_config(tiny, name=f"tiny-{family}")


_MODELS = {}


def model(family):
    """(cfg, params) of a family, made once for the file."""
    if family not in _MODELS:
        cfg = model_of(family)
        _MODELS[family] = cfg, init_params(cfg, jax.random.PRNGKey(41),
                                           dtype=jnp.float32)
    return _MODELS[family]


# -- the forward --------------------------------------------------------------------- #

@pytest.mark.parametrize("rows,chunk", [(1, 24), (4, 64)],
                         ids=["one-row", "four-by-64"])
@pytest.mark.parametrize("family", ["dense", "smallthinker", "deepseek_v3",
                                    "xing4_0"])
def test_forward_runs_the_head_only_where_a_row_samples(family, rows, chunk):
    """No row samples: the parent's cache and stats, zeros where the logits
    were.  One row samples (of three and a pad row in the `[4, 64]` step):
    the parent's logits for every row, its cache and stats.  The parent is
    the same forward without the operand, which has no conditional."""
    cfg, params = model(family)
    stats = cfg.is_moe
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(4, 250, (rows, chunk)), jnp.int32)
    pages = chunk // PAGE + 1
    table = jnp.arange(1, 1 + rows * pages, dtype=jnp.int32).reshape(
        rows, pages)
    prefix = jnp.zeros((rows,), jnp.int32)
    # three rows of their own lengths and a pad row (1 token into trash)
    lens = jnp.asarray([chunk, chunk - 5, 9, 1][:rows], jnp.int32)
    if rows > 1:
        table = table.at[-1].set(0)

    def run(samples):
        kv = KVCache.create(cfg, 2 + rows * pages, PAGE, jnp.float32)
        fwd = jax.jit(lambda p, kv, *a: llama.forward_prefill(
            p, cfg, kv, *a, moe_stats=stats, samples=samples))
        return fwd(params, kv, tokens, table, prefix, lens)

    want_logits, want_kv, *want_st = run(None)
    assert float(jnp.abs(want_logits).max()) > 0
    for sampling in ([], [1] if rows > 1 else [0]):
        rows_sample = np.zeros((rows,), bool)
        rows_sample[sampling] = True
        logits, kv, *st = run(jnp.asarray(rows_sample))
        for got, want in zip(jax.tree.leaves((kv, st)),
                             jax.tree.leaves((want_kv, want_st))):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert logits.shape == want_logits.shape
        assert logits.dtype == want_logits.dtype
        if sampling:
            np.testing.assert_array_equal(np.asarray(logits),
                                          np.asarray(want_logits))
        else:
            assert not np.asarray(logits).any()


# -- the step's program ---------------------------------------------------------------- #

def eqns_of(jaxpr, inside=()):
    """Every equation of a jaxpr and of the jaxprs inside it, each with the
    primitives it lies under."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns_of(sub, inside + (eqn.primitive.name,))


def step_operands(cfg, rows, chunk, pages):
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0),
                                                jnp.float32))
    kv = jax.eval_shape(lambda: KVCache.create(cfg, 16, PAGE, jnp.float32))
    samp = SamplingParams(f32(rows), i32(rows), f32(rows), f32(rows),
                          f32(rows))
    return (params, kv, i32(rows, chunk), i32(rows, pages), i32(rows),
            i32(rows), samp, jax.ShapeDtypeStruct((rows,), jnp.uint32),
            i32(rows))


@pytest.mark.parametrize("with_top", [False, True], ids=["plain", "top"])
@pytest.mark.parametrize("family", ["dense", "xing4_0"])
def test_the_step_holds_one_conditional_with_the_head_inside(family,
                                                             with_top):
    """One `cond` in the whole prefill step, and whatever is as wide as the
    vocabulary (the matmul, the sampling, the logprobs, the top columns)
    lies inside its branches, not beside it; so does the streams' head
    reduction.  Without the operand the same step holds no `cond`."""
    cfg = model_of(family)
    layout = Layout.resolve(cfg, EngineConfig(
        page_size=PAGE, num_pages=16, attention_impl="xla"))[0]
    step = layout.prefill_step(with_top, greedy=False)
    ops = step_operands(cfg, 1, 16, 4)
    rows = jax.ShapeDtypeStruct((1,), jnp.bool_)
    eqns = list(eqns_of(jax.make_jaxpr(step)(*ops, rows).jaxpr))
    # (the sampler's own all-greedy `cond` lies inside it)
    assert sum(e.primitive.name == "cond" and "cond" not in inside
               for e, inside in eqns) == 1
    wide = [(e, inside) for e, inside in eqns for v in e.outvars
            if getattr(v.aval, "shape", ()) and
            v.aval.shape[-1] == cfg.vocab_size]
    assert any(e.primitive.name == "dot_general" for e, _ in wide)
    assert all("cond" in inside for _, inside in wide)
    bare = list(eqns_of(jax.make_jaxpr(step)(*ops).jaxpr))
    assert any(e.primitive.name == "dot_general" and "cond" not in inside
               and e.outvars[0].aval.shape[-1] == cfg.vocab_size
               for e, inside in bare)


# -- the engine ---------------------------------------------------------------------- #

def tiny_engine(family="dense", **over):
    cfg, params = model(family)
    ecfg = dict(page_size=PAGE, num_pages=128, max_num_seqs=8,
                max_prefill_tokens=16, max_model_len=128, decode_steps=2)
    ecfg.update(over)
    return JaxEngine(cfg, params, EngineConfig(**ecfg), eos_token_ids=[],
                     kv_dtype=jnp.float32)


def every_row_samples(engine):
    """The same engine with the operand forced to "every row samples": the
    parent's program, which runs the head on every step."""
    engine.layout.heads_by_rows = False
    return engine


async def generate(engine, prompt, n, sampling, top=0):
    toks, logps, tops = [], [], []
    opts = dict(sampling, logprobs=True)
    if top:
        opts["top_logprobs"] = top
    async for d in engine.generate({
            "token_ids": prompt, "sampling_options": opts,
            "stop_conditions": {"max_tokens": n, "ignore_eos": True}}):
        assert d.get("finish_reason") != "error", d
        toks.extend(d.get("token_ids", []))
        logps.extend(d.get("log_probs", []))
        tops.extend(d.get("top_logprobs", []) or [])
    return toks, logps, tops


def slices(engine, kind):
    return [e for e in engine.events.dump()["events"] if e["kind"] == kind]


SAMPLING = [{"temperature": 0.0}, {"temperature": 1.0, "seed": 41}]


@pytest.mark.parametrize("family", ["dense", "deepseek_v3"])
@pytest.mark.parametrize("sampling", SAMPLING, ids=["greedy", "seeded"])
async def test_a_prompt_of_three_chunks_runs_the_head_once(family, sampling):
    """Tokens, logprobs and top logprobs of a 40-token prompt (chunks of
    16, 16 and 8) equal those of a run that heads every step; the first two
    `prefill_chunk` slices say `head` 0 and the last `head` 1; the counter
    reads 2; as many programs were compiled."""
    prompt = [1 + (5 * i) % 250 for i in range(40)]
    engine, parent = tiny_engine(family), every_row_samples(
        tiny_engine(family))
    try:
        got = await generate(engine, prompt, 3, sampling, top=3)
        want = await generate(parent, prompt, 3, sampling, top=3)
    finally:  # a step's slice is recorded after its tokens went out
        await asyncio.gather(engine.shutdown(), parent.shutdown())
    assert got == want  # ids and float logprobs, bit for bit
    assert len(got[0]) == 3
    assert [e["head"] for e in slices(engine, "prefill_chunk")] == [0, 0, 1]
    assert [e["head"] for e in slices(parent, "prefill_chunk")] == [1, 1, 1]
    m, pm = vars(engine.metrics()), vars(parent.metrics())
    assert (m["prefill_steps_total"],
            m["prefill_steps_headless_total"]) == (3, 2)
    assert pm["prefill_steps_headless_total"] == 0
    # one program a (bucket, table, variant) as before: an operand, not a
    # second compile
    programs = [sorted((key, e.layout.prefill_step(*key)._cache_size())  # noqa: SLF001
                       for key in e.layout.compiled_variants["prefill"])
                for e in (engine, parent)]
    assert programs[0] == programs[1] and programs[0]
    if family == "deepseek_v3":  # the stats ride outside the conditional
        assert [e["experts_hit"] for e in slices(engine, "prefill_chunk")] == [
            e["experts_hit"] for e in slices(parent, "prefill_chunk")]


async def test_a_shared_short_step_always_runs_the_head():
    """Short prompts that share a `[4, 64]`-form step are whole remaining
    prompts: every such step has a sampling row."""
    sizes = dict(max_prefill_tokens=128, max_model_len=256)  # short: 16
    engine = tiny_engine(**sizes)
    parent = every_row_samples(tiny_engine(**sizes))
    prompts = [[3 + (c + 3 * i) % 200 for i in range(9 + c)]
               for c in range(3)]
    try:
        got = await asyncio.gather(*(
            generate(engine, p, 2, SAMPLING[0]) for p in prompts))
        want = await asyncio.gather(*(
            generate(parent, p, 2, SAMPLING[0]) for p in prompts))
    finally:
        await asyncio.gather(engine.shutdown(), parent.shutdown())
    assert got == want
    chunks = slices(engine, "prefill_chunk")
    assert any(e["batch"] > 1 for e in chunks)
    assert all(e["head"] == 1 for e in chunks)
    assert vars(engine.metrics())["prefill_steps_headless_total"] == 0


@pytest.mark.parametrize("sampling", SAMPLING, ids=["greedy", "seeded"])
async def test_a_mixed_step_still_samples_a_prompts_last_chunk(sampling):
    """The operand reaches `mixed_body`: while one request decodes, a
    40-token prompt's chunks ride mixed steps, of which the mid-prompt ones
    say `head` 0 and the last `head` 1, and both streams are those of a run
    that heads every step.

    Token for token; the logprobs to float32 rounding and not to the bit
    (PR 43): WHICH decode token of the first stream the second prompt's
    chunks join is the event loop's timing against the step thread, not the
    program's, and a token computed on the decode side of a mixed step and
    the same token computed by a decode block are two programs, whose
    logprobs differ in the last place (-4.5032697 against -4.5032701 on one
    of 24 tokens, the ids alike).  The engine and its twin race that
    separately, so a comparison to the bit failed about every other run,
    alone on a quiet machine too."""
    first = [2 + (3 * i) % 250 for i in range(12)]
    second = [1 + (7 * i) % 250 for i in range(40)]

    async def serve(engine):
        try:
            a = asyncio.ensure_future(generate(engine, first, 24, sampling))
            while not slices(engine, "decode_block"):
                await asyncio.sleep(0.01)
            b = await generate(engine, second, 4, sampling)
            return await a, b
        finally:
            await engine.shutdown()

    engine = tiny_engine(mixed_prefill_tokens=16)
    parent = every_row_samples(tiny_engine(mixed_prefill_tokens=16))
    got, want = await serve(engine), await serve(parent)
    for (toks, logps, tops), (w_toks, w_logps, w_tops) in zip(got, want):
        assert toks == w_toks and tops == w_tops
        np.testing.assert_allclose(logps, w_logps, rtol=1e-5, atol=1e-5)
    assert len(got[1][0]) == 4
    heads = [e["head"] for e in slices(engine, "mixed_step")]
    assert heads.count(1) == 1 and heads.count(0) >= 1, heads
    assert all(e["head"] == 1 for e in slices(parent, "mixed_step"))
