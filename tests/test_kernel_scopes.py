"""The kernels name their steps (``observability/trace.py``
``kernel_scope``) and the kernel cache exports each compiled kernel's
instruction -> scope map (``executor/kernel_cache.py``
``export_kernel_scopes``): what gives a device trace's ``while.8`` and
``fusion.26`` their roles.

TPC-H Q12's two tables in small (``tests/test_join_exchange.py``'s) on
one device and on four of the harness's: a direct GROUP BY (``jit_fused``
/ on the mesh ``jit_run``), a hash GROUP BY (``jit_hash_fused``) and the
repartition join (``jit_join_probe``, ``jit_join_build``, on the mesh
``jit_join_exchange``).
"""

import contextlib
import glob
import json
import os
import re

import jax
import numpy as np
import pytest

import citus_tpu as ct
from benchmarks.generators import tpch_q12_tables as G
from citus_tpu.executor import kernel_cache as KC
from citus_tpu.executor.kernel_cache import GLOBAL_KERNELS
from citus_tpu.observability import trace as T

from test_join_exchange import LINEITEM, ORDERS_DDL, PARAMS, SHARDS, q12

DIRECT = ("select l_returnflag, l_linestatus, count(*), sum(l_quantity) "
          "from lineitem where l_shipdate < date '1998-09-02' "
          "group by l_returnflag, l_linestatus")
HASH = ("select l_orderkey, sum(l_quantity), min(l_tax) from lineitem "
        "where l_quantity < 40 group by l_orderkey")

#: module -> the scopes its body names, in the body's order (ISSUE 50)
SCOPES = {
    "jit_hash_fused": ["hash.keys", "hash.sort", "hash.gather",
                       "hash.segments", "hash.ends", "hash.offer"],
    "jit_join_probe": ["probe.lanes", "probe.pack", "probe.lookup",
                       "probe.block", "probe.payload"],
    "jit_fused": ["scan.env", "scan.filter", "scan.group_id",
                  "scan.reduce", "scan.fold"],
    "jit_run": ["scan.env", "scan.filter", "scan.group_id", "scan.reduce",
                "scan.fold"],
    "jit_join_build": ["build.keys", "build.insert"],
    "jit_join_exchange": ["exchange.target", "exchange.pack",
                          "exchange.all_to_all", "build.keys",
                          "build.insert"],
}


@pytest.fixture(scope="module")
def cl(tmp_path_factory):
    cluster = ct.Cluster(str(tmp_path_factory.mktemp("scopes") / "db"))
    cluster.execute(LINEITEM)
    cluster.execute(f"SELECT create_distributed_table('lineitem', "
                    f"'l_orderkey', {SHARDS})")
    cluster.execute(ORDERS_DDL)
    cluster.execute(f"SELECT create_distributed_table('orders', "
                    f"'o_custkey', {SHARDS})")
    for i in range(G.n_chunks(PARAMS)):
        chunk = G.generate_chunk(PARAMS, PARAMS["data_seed"], i)
        for table, columns in G.copy_columns(chunk).items():
            cluster.copy_from(table, columns=columns)
    # the hash table, not the direct one, for the order key
    cluster.execute("SET citus.direct_gid_limit = 16")
    return cluster


def fresh_kernels(cl):
    """Every kernel of the statements below compiles anew."""
    from citus_tpu.executor.device_cache import GLOBAL_CACHE
    GLOBAL_KERNELS.clear()
    GLOBAL_CACHE.clear()
    cl._plan_cache.clear()


def run_all(cl):
    answers = [cl.execute(sql).rows for sql in (DIRECT, HASH, q12())]
    assert all(answers)
    return answers


def variants(module=None):
    """[(kernel, variant)] of the live scoped kernels."""
    with KC._kernels_mu:
        kernels = list(KC._kernels)
    return [(k, v) for k in kernels for v in k._variants
            if module in (None, k.module)]


def optimized_text(kernel, variant) -> str:
    _, (args, kw) = variant
    text = kernel._fn.lower(*args, **kw).compile().as_text()
    T.take_kernel_scopes()
    return text


_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")


def instructions(text: str) -> list:
    """The module's lines without what a scope may change: the
    ``metadata={...}`` of an instruction and the stack-frame tables
    (``FileNames`` ... ``StackFrames``) that stand before the first
    computation."""
    lines = text.splitlines()
    first = next(i for i, ln in enumerate(lines)
                 if ln.endswith("{") and " -> " in ln)
    return [lines[0]] + [_METADATA.sub("", ln) for ln in lines[first:]]


def maps_in(directory) -> dict:
    """{module: [the maps of its variants]} of an exported directory."""
    out = {}
    for path in sorted(glob.glob(os.path.join(directory, "*.scopes.json"))):
        with open(path) as fh:
            m = json.load(fh)
        assert os.path.basename(path).startswith(m["module"] + ".")
        out.setdefault(m["module"], []).append(m)
    return out


def scopes_of(m) -> set:
    return {op["scope"] for op in m["ops"].values() if op["scope"]}


# ------------------------------- (a) a scope changes no instruction


@pytest.fixture()
def no_compile_cache():
    """JAX keys its persistent compile cache without the instructions'
    metadata: with it on, the kernels without scopes would be served the
    executables compiled with them, and the comparison would hold one
    text against itself."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("module", ["jit_hash_fused", "jit_join_probe",
                                    "jit_fused"])
def test_a_scope_changes_no_instruction(cl, limit_devices, monkeypatch,
                                        no_compile_cache, module):
    limit_devices(1)
    fresh_kernels(cl)
    earlier = {id(k) for k, _ in variants()}
    expected = run_all(cl)
    with_scopes = [optimized_text(k, v) for k, v in variants(module)
                   if id(k) not in earlier]
    assert with_scopes and all("citus." in t for t in with_scopes)

    # the same kernels with every scope a no-op (the count still moves,
    # so the signatures are remembered the same way)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    old = {id(k) for k, _ in variants()}
    fresh_kernels(cl)
    assert run_all(cl) == expected
    without = [optimized_text(k, v) for k, v in variants(module)
               if id(k) not in old]
    assert len(without) == len(with_scopes)
    assert not any("citus." in t for t in without)
    key = lambda t: instructions(t)[0]
    for a, b in zip(sorted(with_scopes, key=key), sorted(without, key=key)):
        assert instructions(a) == instructions(b)


def test_a_cache_filled_without_scopes_still_gives_the_names(
        cl, limit_devices, monkeypatch, tmp_path):
    """JAX keys its persistent cache without the metadata, so a cache
    that a build without scopes filled serves that build's executable
    to the kernels with them: the export then compiles under a key of
    its own, once, and a later process finds that entry."""
    from jax.experimental.compilation_cache import compilation_cache
    limit_devices(1)
    home = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    compilation_cache.reset_cache()
    try:
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            fresh_kernels(cl)
            cl.execute(DIRECT)
        fresh_kernels(cl)
        earlier = {id(k) for k, _ in variants()}
        cl.execute(DIRECT)
        (kernel, variant), = [(k, v) for k, v in variants("jit_fused")
                              if id(k) not in earlier]
        # what the jitted call was served holds no name ...
        assert "citus." not in optimized_text(kernel, variant)
        # ... the export's text does, compiled under a key of its own
        path = KC._write_scope_map(kernel, variant, str(tmp_path / "maps"))
        with open(path) as fh:
            assert scopes_of(json.load(fh)) == set(SCOPES["jit_fused"])
        assert not jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_dir", home)
        compilation_cache.reset_cache()


# ------------------------------- (b) every scope reaches its module


@pytest.mark.parametrize("n_dev", [1, 4])
def test_every_scope_is_in_its_modules_map(cl, limit_devices, tmp_path,
                                           n_dev):
    limit_devices(n_dev)
    fresh_kernels(cl)
    before = {id(k) for k, _ in variants()}
    run_all(cl)
    out = tmp_path / "maps"
    written = [p for (k, v) in variants() if id(k) not in before
               for p in [KC._write_scope_map(k, v, str(out))]]
    assert written
    maps = maps_in(out)
    expected = ["jit_fused", "jit_hash_fused", "jit_join_probe",
                "jit_join_build"] if n_dev == 1 else \
        ["jit_run", "jit_hash_fused", "jit_join_probe", "jit_join_exchange"]
    for module in expected:
        assert module in maps, (module, sorted(maps))
        found = set().union(*(scopes_of(m) for m in maps[module]))
        assert found == set(SCOPES[module]), module
    for (k, v) in variants():
        if id(k) in before:
            continue
        for m in re.finditer(r'op_name="([^"]*)"', optimized_text(k, v)):
            # scopes do not nest: one citus. component a name (XLA joins
            # the names of instructions it merged with ;)
            for name in m.group(1).split(";"):
                assert name.count("citus.") <= 1, m.group(1)


def test_the_numpy_arm_runs_the_bodies_and_names_nothing(cl, monkeypatch):
    def never(name):
        raise AssertionError(f"named_scope({name!r}) on the numpy arm")
    monkeypatch.setattr(jax, "named_scope", never)
    cl._plan_cache.clear()
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    try:
        T.take_kernel_scopes()
        rows = cl.execute(DIRECT).rows
        assert rows and T.take_kernel_scopes() == 0
    finally:
        cl.execute("SET citus.task_executor_backend = 'tpu'")
    assert T.kernel_scope(np, "scan.env") is T.kernel_scope(np, "scan.fold")
    monkeypatch.undo()
    assert sorted(cl.execute(DIRECT).rows) == sorted(rows)


# ------------------------------- (c) the map of a canned module


CANNED = '''HloModule jit_canned, is_scheduled=true, entry_computation_layout={(s32[8]{0})->(s32[8]{0}, s32[])}

FileNames
1 "ops/hash_agg.py"

%fused_computation (param_0.1: s32[8], param_1: s32[8]) -> s32[8] {
  %param_0.1 = s32[8]{0} parameter(0)
  %param_1 = s32[8]{0} parameter(1)
  %gather.3 = s32[8]{0} gather(%param_0.1, %param_1), metadata={op_name="jit(canned)/citus.hash.gather/gather" source_file="a.py" source_line=3}
  ROOT %add.2 = s32[8]{0} add(%gather.3, %param_1), metadata={op_name="jit(canned)/citus.hash.segments/add"}
}

%fused_computation.1 (param_0.2: s32[8]) -> s32[8] {
  %param_0.2 = s32[8]{0} parameter(0)
  ROOT %neg.1 = s32[8]{0} negate(%param_0.2), metadata={op_name="jit(canned)/citus.hash.offer/while/body/neg"}
}

%body.5 (arg: (s32[], s32[8])) -> (s32[], /*index=1*/s32[8]) {
  %arg = (s32[], s32[8]{0}) parameter(0)
  %gte.1 = s32[8]{0} get-tuple-element(%arg), index=1
  %fusion.7 = s32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(canned)/citus.hash.offer/while/body/neg"}
  %copy.9 = s32[8]{0} copy(%fusion.7)
  ROOT %tuple.2 = (s32[], /*index=1*/s32[8]{0}) tuple(%gte.0, %copy.9)
}

%cond.6 (arg.1: (s32[], s32[8])) -> pred[] {
  %arg.1 = (s32[], s32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%gte.2, %c), direction=LT, metadata={op_name="jit(canned)/citus.hash.offer/while/cond/lt"}
}

%region_sum (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %add.9 = s32[] add(%a, %b)
}

ENTRY %main.9 (p: s32[8]) -> (s32[8], s32[]) {
  %p = s32[8]{0} parameter(0)
  %sort.4 = s32[8]{0} sort(%p), dimensions={0}, to_apply=%region_sum, metadata={op_name="jit(canned)/citus.hash.sort/sort"}
  %fusion.3 = s32[8]{0} fusion(%sort.4, %p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(canned)/citus.hash.segments/add"}
  %while.8 = (s32[], /*index=1*/s32[8]{0}) while(%tuple.1), condition=%cond.6, body=%body.5, metadata={op_name="jit(canned)/citus.hash.offer/while"}
  %conditional.1 = s32[8]{0} conditional(%pr, %x, %y), true_computation=%body.5, false_computation=%cond.6
  %conditional.2 = s32[8]{0} conditional(%i, %x, %y), branch_computations={%body.5, %cond.6}, metadata={op_name="jit(canned)/vmap(citus.probe.block)/cond"}
  %add.5 = s32[8]{0} add(%p, %p), metadata={op_name="jit(canned)/citus.hash.segments/add;jit(canned)/citus.hash.ends/add"}
  %reduce.5 = s32[] reduce(%p, %zero), dimensions={0}, to_apply=%region_sum, metadata={op_name="jit(canned)/jit(shmap_body)/citus.scan.fold/reduce_sum"}
  ROOT %tuple.3 = (s32[8]{0}, s32[]) tuple(%fusion.3, %reduce.5)
}
'''


def test_the_map_of_a_canned_module():
    ops = KC.scope_map(CANNED)
    plain = {"inside": [], "calls": []}
    # a fusion counts under its root's scope and lists what it holds
    assert ops["fusion.3"] == {"scope": "hash.segments", "calls": [],
                               "inside": ["hash.gather", "hash.segments"]}
    assert ops["fusion.7"] == {"scope": "hash.offer", "calls": [],
                               "inside": ["hash.offer"]}
    # a while names the computations it runs; its body's ops are ops
    assert ops["while.8"] == {"scope": "hash.offer", "inside": [],
                              "calls": ["cond.6", "body.5"]}
    assert ops["neg.1"]["scope"] == "hash.offer"
    assert ops["lt.1"]["scope"] == "hash.offer"
    assert ops["conditional.1"] == {"scope": None, "inside": [],
                                    "calls": ["body.5", "cond.6"]}
    assert ops["conditional.2"] == {"scope": "probe.block", "inside": [],
                                    "calls": ["body.5", "cond.6"]}
    # an instruction XLA made carries no metadata: no scope
    assert ops["copy.9"] == {"scope": None, **plain}
    # an instruction XLA merged of two steps' says both
    assert ops["add.5"] == {"scope": "hash.ends", "calls": [],
                            "inside": ["hash.ends", "hash.segments"]}
    assert ops["tuple.2"] == {"scope": None, **plain}
    # a sort's or a reduce's to_apply is no op of a trace
    assert ops["sort.4"] == {"scope": "hash.sort", **plain}
    assert ops["reduce.5"] == {"scope": "scan.fold", **plain}
    assert set(ops) >= {"p", "param_0.1", "add.9", "tuple.3"}
    assert "FileNames" not in ops and "main.9" not in ops
    # what every execution runs: the entry computation's fusions, sorts,
    # loops and conditionals (a reader tells variants apart by them)
    assert KC._parse_module(CANNED)[1] == [
        "sort.4", "fusion.3", "while.8", "conditional.1", "conditional.2"]


# ------------------------------- (d) when the maps are written


def test_set_trace_export_dir_writes_the_maps_beside_it(cl, limit_devices,
                                                        tmp_path):
    limit_devices(1)
    fresh_kernels(cl)
    spans = tmp_path / "spans"
    kernels = tmp_path / "spans.kernels"
    cl.execute("SET citus.trace_sample_rate = 0")
    try:
        # the setting empty: nothing is written, signatures are kept only
        # at a compile, and the unsampled path allocates no span
        run_all(cl)
        assert not kernels.exists() and not spans.exists()
        kept = [(k, len(k._variants)) for k, _ in variants()]
        before = T.span_allocations()
        run_all(cl)
        assert T.span_allocations() == before
        assert [(k, len(k._variants)) for k, _ in variants()] == kept

        # the kernels compiled before the SET
        cl.execute(f"SET citus.trace_export_dir = '{spans}'")
        first = maps_in(kernels)
        assert {"jit_fused", "jit_hash_fused", "jit_join_probe",
                "jit_join_build"} <= set(first)
        # ... and nothing INTO the span directory: who empties it removes
        # files, and its readers list it
        assert not spans.exists() or not os.listdir(spans)
        for module, ms in first.items():
            for m in ms:
                assert set(m) >= {"module", "signature", "entry", "ops"}
                assert m["entry"] and set(m["entry"]) <= set(m["ops"])
                assert all(set(op) == {"scope", "inside", "calls"}
                           for op in m["ops"].values())
        # a donated argument's signature was read after its buffer went
        # (jit_hash_fused donates its table)
        assert "int64[" in first["jit_hash_fused"][0]["signature"]

        # a kernel that compiles while the directory is set writes then
        n = len(glob.glob(str(kernels / "jit_fused.*")))
        cl.execute("select l_linestatus, count(*) from lineitem "
                   "group by l_linestatus")
        assert len(glob.glob(str(kernels / "jit_fused.*"))) == n + 1
        # the same SET again rewrites the files and adds none
        listed = sorted(os.listdir(kernels))
        cl.execute(f"SET citus.trace_export_dir = '{spans}'")
        assert sorted(os.listdir(kernels)) == listed

        # the setting emptied: a compile writes nothing
        cl.execute("SET citus.trace_export_dir = ''")
        cl.execute("select l_returnflag, count(*) from lineitem "
                   "group by l_returnflag")
        assert sorted(os.listdir(kernels)) == listed
    finally:
        cl.execute("SET citus.trace_export_dir = ''")


def test_an_unscoped_kernel_is_not_remembered(cl, limit_devices):
    """``jit_filter`` retraces a bucket at a time in the lookup cell:
    nothing of it is kept, so nothing of it is ever lowered twice."""
    limit_devices(1)
    fresh_kernels(cl)
    c0 = cl.counters.snapshot()
    assert cl.execute("select l_orderkey, l_quantity from lineitem "
                      "where l_quantity < 3 and l_tax * 2 > 0.1").rows
    assert cl.counters.snapshot()["kernel_compiles"] > c0["kernel_compiles"]
    with KC._kernels_mu:
        unscoped = [k for k in KC._kernels if k.module == "jit_device_mask"]
    assert unscoped and not any(k._variants for k in unscoped)


def test_profile_leaves_the_maps_with_the_trace(cl, limit_devices, tmp_path):
    limit_devices(1)
    fresh_kernels(cl)
    out = tmp_path / "profile"
    assert cl.profile(DIRECT, str(out)).rows
    # (every live scoped kernel's map is there: this statement's among them)
    assert set(SCOPES["jit_fused"]) in [
        scopes_of(m) for m in maps_in(out / "kernels")["jit_fused"]]


# ------------------------------- (e) what recompiled, and for what


def test_the_kernel_compile_span_says_which_module_and_shapes(cl,
                                                              limit_devices):
    limit_devices(1)
    fresh_kernels(cl)
    cl.execute("SET citus.trace_sample_rate = 1")
    try:
        cl.execute(HASH)
        trace = T.last_trace()
    finally:
        cl.execute("SET citus.trace_sample_rate = 0")
    compiles = {s.attrs["module"]: s.attrs["shapes"]
                for s in trace.find_all("kernel_compile")}
    assert "jit_hash_fused" in compiles
    for shapes in compiles.values():
        assert 0 < len(shapes) <= 120
    assert re.match(r"^int64\[\d+\] ", compiles["jit_hash_fused"])
