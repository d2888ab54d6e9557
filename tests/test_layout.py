"""The serving layout lives behind one module (ISSUE 33): `engine.py` keeps
the loop and names no sharding, `Layout.resolve` makes the refusals and the
config rewrites `JaxEngine.__init__` used to make, and every layout's
prefill step wraps the one `steps.prefill_body`."""

import ast
import dataclasses
import pathlib

import pytest

from dynamo_tpu.engine import EngineConfig, layout as layout_mod, steps
from dynamo_tpu.engine.layout import Layout
from dynamo_tpu.models import tiny_config, tiny_moe_config
from dynamo_tpu.parallel import ParallelConfig

ENGINE_DIR = pathlib.Path(layout_mod.__file__).parent


def _tree(name):
    return ast.parse((ENGINE_DIR / name).read_text())


def _called(tree):
    """Names called anywhere in the module: `f(...)` and `x.f(...)`."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            out.add(f.id if isinstance(f, ast.Name)
                    else f.attr if isinstance(f, ast.Attribute) else None)
    return out


def test_engine_names_no_sharding_and_builds_no_program():
    tree = _tree("engine.py")
    defined = [n.name for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    assert not [n for n in defined if n.startswith("_build_")
                or (n.startswith("_get_") and n.endswith("_step"))]
    assert not _called(tree) & {"NamedSharding", "shard_map", "P",
                                "PartitionSpec"}
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            imported.append("." * node.level + (node.module or ""))
        elif isinstance(node, ast.Import):
            imported += [a.name for a in node.names]
    assert not [m for m in imported
                if m.startswith(("jax.sharding", "..parallel"))], imported
    init = next(n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)
                and n.name == "__init__"
                and n.args.args[1].arg == "model_cfg")
    assert init.end_lineno - init.lineno < 200


def test_the_sampling_tail_is_written_once_per_body():
    """forward → sample → logprobs → pack: once for prefill (the mixed
    step's prefill side calls it), once for each decode scan."""
    calls = {name: sum(isinstance(n, ast.Call)
                       and getattr(n.func, "id", None)
                       == "sample_tokens_maybe_greedy"
                       for n in ast.walk(_tree(name)))
             for name in ("engine.py", "layout.py", "steps.py")}
    assert calls == {"engine.py": 0, "layout.py": 0, "steps.py": 3}


# -- Layout.resolve: the rewrites and the refusals of the parent's __init__ --- #

BASE = dict(max_num_seqs=6, decode_batch_buckets=[1, 3, 6], max_model_len=64,
            max_prefill_tokens=64)


def _rounded(to):
    return sorted({-(-b // to) * to for b in BASE["decode_batch_buckets"]})


@pytest.mark.parametrize("parallel,over,want", [
    (None, {}, {}),
    (None, {"prefill_batch_size": 2}, {}),
    (ParallelConfig(dp=2, tp=2), {}, {"decode_batch_buckets": _rounded(2)}),
    (ParallelConfig(dp=2), {"kv_partition": True, "prefill_batch_size": 2},
     {"fuse_prefill_decode": False}),
    (ParallelConfig(dp=2, tp=2), {"kv_partition": True},
     {"fuse_prefill_decode": False}),
    (ParallelConfig(pp=2, dp=2), {},
     {"fuse_prefill_decode": False, "mixed_prefill_tokens": 0,
      "decode_batch_buckets": _rounded(4)}),
    (ParallelConfig(pp=2, dp=2), {"kv_partition": True},
     {"fuse_prefill_decode": False, "mixed_prefill_tokens": 0,
      "decode_batch_buckets": _rounded(2)}),
    (ParallelConfig(sp=2, dp=2), {},
     {"mixed_prefill_tokens": 0, "decode_batch_buckets": _rounded(2)}),
], ids=["flat", "flat-rows-given", "dpxtp", "pooled-rows-given", "pooled",
        "pp", "pp-pooled", "sp"])
def test_resolve_rewrites_the_config_as_init_did(parallel, over, want):
    given = EngineConfig(**BASE, **over)
    layout, cfg = Layout.resolve(tiny_config(), given, parallel)
    # short chunks share a step on the flat single-process engine alone;
    # every other layout keeps one sequence a step whatever it was told
    if parallel is not None:
        want = {**want, "prefill_batch_size": 1}
    assert cfg == dataclasses.replace(given, **want)
    assert layout.cfg is cfg
    assert layout.pooled == bool(over.get("kv_partition"))
    assert (layout.mesh is None) == (parallel is None)
    if parallel is not None:
        assert (layout.dp, layout.sp, layout.pp) == (
            parallel.dp, parallel.sp, parallel.pp)
    assert layout.pool_ranks == (parallel.dp if layout.pooled else 1)


MROPE = tiny_config(mrope_section=(2, 3, 3))


@pytest.mark.parametrize("model,parallel,over,kw,message", [
    (tiny_config(), None, {}, {"multihost": True},
     "multihost requires a ParallelConfig spanning the global device set "
     "(dp*tp*sp == jax.device_count())"),
    (tiny_config(), ParallelConfig(pp=4), {}, {},
     "pp=4 must divide num_hidden_layers=2"),
    (tiny_config(), ParallelConfig(pp=2, dp=2), {}, {"vision": object()},
     "pp does not support the vision tower yet"),
    (tiny_config(), ParallelConfig(pp=2, tp=4), {}, {},
     "tp=4 must evenly divide kv heads for pp×tp serving"),
    (tiny_config(), ParallelConfig(sp=2, dp=2), {"kv_partition": True}, {},
     "sp > 1 with kv_partition requires enable_prefix_caching=False "
     "(prefix pages are owner-shard-local)"),
    (tiny_config(), ParallelConfig(sp=2), {"max_prefill_tokens": 32}, {},
     "sp > 1 requires max_prefill_tokens >= max_model_len — no prompt may "
     "be split into chunks"),
    (tiny_config(), ParallelConfig(sp=4), {"chunk_buckets": [2, 64]}, {},
     "chunk buckets [2] not divisible by sp=4"),
    (tiny_moe_config(moe_impl="capacity"), ParallelConfig(sp=2, tp=2), {},
     {}, "sp×tp MoE requires moe_impl='auto'|'ragged'|'a2a' and "
     "num_experts divisible by tp"),
    (tiny_config(), ParallelConfig(sp=2, tp=4), {}, {},
     "tp=4 must evenly divide kv heads for sp×tp prefill"),
    (tiny_config(), ParallelConfig(dp=2),
     {"kv_partition": True, "decode_batch_buckets": [1, 2]}, {},
     "kv_partition requires max(decode_batch_buckets)=2 >= max_num_seqs=6"),
    (tiny_config(), None, {"kv_partition": True}, {},
     "kv_partition requires a serving mesh (ParallelConfig with "
     "dp*sp > 1)"),
    (tiny_config(), ParallelConfig(tp=2), {"fuse_projections": True}, {},
     "fuse_projections is single-device only (the fused output axis does "
     "not carry the megatron tp specs)"),
    (MROPE, ParallelConfig(pp=2), {}, {},
     "mrope models do not serve under pp yet"),
    (tiny_config(), ParallelConfig(tp=2), {"attention_impl": "pallas"}, {},
     "the Pallas attention kernels are per-shard programs; a GSPMD-meshed "
     "engine must use attention_impl='xla'"),
], ids=["multihost-flat", "pp-layers", "pp-vision", "ppxtp-uneven",
        "sp-pooled-prefix-cache", "sp-chunked", "sp-buckets", "spxtp-moe",
        "spxtp-uneven", "pooled-buckets", "pooled-flat", "fused-meshed",
        "mrope-pp", "pallas-meshed"])
def test_resolve_refuses_what_init_refused(model, parallel, over, kw,
                                           message):
    with pytest.raises(ValueError) as err:
        Layout.resolve(model, EngineConfig(**{**BASE, **over}), parallel,
                       **kw)
    assert str(err.value) == message


def test_pp_with_a_partitioned_pool_refuses_sp():
    """`make_mesh` refuses pp×sp before `resolve` reaches this one; it
    stays for a mesh that learns to build one."""
    with pytest.raises(ValueError) as err:
        Layout._resolve_meshed(
            tiny_config(), EngineConfig(**BASE, kv_partition=True),
            ParallelConfig(pp=2, sp=2), None)
    assert str(err.value) == (
        "pp×kv_partition partitions pages over dp only (sp within a stage "
        "is future work)")


# -- one prefill body under every layout ---------------------------------------- #

@pytest.mark.parametrize("parallel,over,name", [
    (None, {}, "prefill_step"),
    (ParallelConfig(dp=2, tp=2), {}, "prefill_step"),
    (ParallelConfig(dp=2, tp=2), {"kv_partition": True},
     "prefill_step_pooled"),
    (ParallelConfig(pp=2, dp=2), {}, "prefill_step_pp"),
    (ParallelConfig(sp=2, dp=2), {}, "prefill_step_sp"),
], ids=["flat", "dpxtp", "pooled", "pp", "sp"])
def test_every_layout_wraps_the_one_prefill_body(monkeypatch, parallel, over,
                                                 name):
    made, wrapped = [], []
    real_body, real_wrap = steps.prefill_body, Layout.wrap

    def body_spy(*args, **kw):
        made.append(real_body(*args, **kw))
        return made[-1]

    def wrap_spy(self, body, *args, **kw):
        wrapped.append(body)
        return real_wrap(self, body, *args, **kw)

    monkeypatch.setattr(steps, "prefill_body", body_spy)
    monkeypatch.setattr(Layout, "wrap", wrap_spy)
    layout, _ = Layout.resolve(tiny_config(), EngineConfig(**BASE, **over),
                               parallel)
    step = layout.prefill_step(False, greedy=True)
    assert step.__name__ == name
    assert len(made) == 1 and wrapped == made
    assert made[0].__code__ is real_body(tiny_config(), None).__code__
    assert layout.prefill_step(False, greedy=True) is step  # cached
    assert layout.compiled_variants["prefill"] == [(False, False, True)]
