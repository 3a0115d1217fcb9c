"""rwlint test surface (docs/static-analysis.md).

Three layers:

1. Fixture snippets — each rule fires on a minimal positive and stays
   quiet on the matching negative. For every migrated grep lint the
   fixtures include (a) a comment/docstring case where the OLD grep
   fired falsely (asserted by running the grep's own regex against the
   fixture) and the AST rule stays quiet, and (b) an aliased-import
   case the OLD grep missed and the AST rule catches — the
   "AST-beats-grep" proof the migration hangs on.
2. Coverage cross-check — the dispatch-discipline closure is computed
   from the STATIC registry parse; asserting it equals the RUNTIME
   ``EPOCH_BUILDERS``/``SHARDED_EPOCH_BUILDERS`` dicts proves every
   builder a tick can resolve is lint-covered.
3. Tier-1 wiring — the whole package lints clean inside the 10 s CI
   timing budget (scripts/check.sh enforces the same budget).
"""

import re
import textwrap
import time

import pytest

from risingwave_tpu.analysis import (RULES, all_rules, lint_package,
                                     load_package, package_root)

all_rules()  # populate the registry once


def lint_fixture(tmp_path, files, rules):
    """Write a throwaway package named risingwave_tpu (rule targets are
    qualified against the real package name) and lint it."""
    root = tmp_path / "risingwave_tpu"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    findings, counts, _ = lint_package(
        root, [RULES[r] for r in rules])
    return findings


DISPATCH_STUB = {
    "stream/dispatch.py": """
        class PermitChannel:
            def __init__(self, permits=8):
                self.permits = permits
        """,
    "stream/__init__.py": "from .dispatch import PermitChannel\n",
}


class TestExchangeBoundary:
    GREP = re.compile(r"PermitChannel\(")

    def test_aliased_import_caught_where_grep_missed(self, tmp_path):
        files = dict(DISPATCH_STUB)
        files["worker/rogue.py"] = """
            from ..stream.dispatch import PermitChannel as PC

            def wire():
                return PC(4)
            """
        src = textwrap.dedent(files["worker/rogue.py"])
        assert not self.GREP.search(src)  # the old grep is blind here
        found = lint_fixture(tmp_path, files, ["exchange-boundary"])
        assert [f.rule for f in found] == ["exchange-boundary"]
        assert found[0].path == "worker/rogue.py"

    def test_reexport_chain_caught(self, tmp_path):
        files = dict(DISPATCH_STUB)
        files["worker/rogue.py"] = """
            from ..stream import PermitChannel

            def wire():
                return PermitChannel(4)
            """
        found = lint_fixture(tmp_path, files, ["exchange-boundary"])
        assert len(found) == 1

    def test_docstring_mention_not_flagged(self, tmp_path):
        files = dict(DISPATCH_STUB)
        files["worker/clean.py"] = '''
            """Frames flow via open_channel, never raw PermitChannel(...)."""

            # a comment saying PermitChannel(8) is not a construction
            def wire(open_channel):
                return open_channel(4)
            '''
        src = textwrap.dedent(files["worker/clean.py"])
        assert self.GREP.search(src)  # the old grep false-positives
        assert lint_fixture(tmp_path, files, ["exchange-boundary"]) == []

    def test_exempt_modules_stay_quiet(self, tmp_path):
        files = dict(DISPATCH_STUB)
        files["frontend/fragments.py"] = """
            from ..stream.dispatch import PermitChannel

            def build():
                return PermitChannel(8)
            """
        assert lint_fixture(tmp_path, files, ["exchange-boundary"]) == []


class TestWireBoundary:
    GREP = re.compile(r"sock\.sendall\(|sock\.recv\(")

    def test_renamed_socket_caught_where_grep_missed(self, tmp_path):
        files = {"meta/rogue.py": """
            def push(conn, payload):
                conn.sendall(payload)
                return conn.recv(4096)
            """}
        src = textwrap.dedent(files["meta/rogue.py"])
        assert not self.GREP.search(src)  # receiver is not named sock
        found = lint_fixture(tmp_path, files, ["wire-boundary"])
        assert len(found) == 2

    def test_comment_and_channel_recv_not_flagged(self, tmp_path):
        files = {"stream/clean.py": '''
            """Raw sock.recv( / sock.sendall( belong to rpc/wire.py."""

            async def pump(ch):
                # not sock.sendall(frame) — the channel owns delivery
                return await ch.recv()
            '''}
        src = textwrap.dedent(files["stream/clean.py"])
        assert self.GREP.search(src)  # grep fired on prose
        assert lint_fixture(tmp_path, files, ["wire-boundary"]) == []

    def test_wire_module_exempt(self, tmp_path):
        files = {"rpc/wire.py": """
            def send_frame(sock, b):
                sock.sendall(b)
                return sock.recv(4)
            """}
        assert lint_fixture(tmp_path, files, ["wire-boundary"]) == []


class TestPlacementMutation:
    GREP = re.compile(r'"placement/')

    def test_fstring_key_and_save_placement_caught(self, tmp_path):
        files = {"worker/rogue.py": """
            def hijack(store, meta, job, p):
                store.put(f"placement/{job}", b"")
                meta.save_placement(p)
            """}
        found = lint_fixture(tmp_path, files, ["placement-mutation"])
        assert len(found) == 2

    def test_docstring_mention_not_flagged(self, tmp_path):
        files = {"worker/clean.py": '''
            """The "placement/" keyspace belongs to meta/service.py."""

            def read_only(meta, job):
                return meta.load_placement(job)
            '''}
        src = textwrap.dedent(files["worker/clean.py"])
        assert self.GREP.search(src)  # grep false-positived on docs
        assert lint_fixture(tmp_path, files, ["placement-mutation"]) == []

    def test_owning_modules_exempt(self, tmp_path):
        files = {
            "meta/service.py": """
                def save_placement(store, key, p):
                    store.put(f"placement/{key}", p)
                """,
            "meta/rescale.py": """
                def commit_placement(meta, p):
                    meta.save_placement(p)
                """,
        }
        assert lint_fixture(tmp_path, files, ["placement-mutation"]) == []


class TestServingCache:
    GREP = re.compile(r"lower_plan\(")

    def test_aliased_lower_plan_caught_where_grep_missed(self, tmp_path):
        files = {
            "batch/lower.py": "def lower_plan(plan, store):\n    pass\n",
            "frontend/session.py": """
                from ..batch.lower import lower_plan as _lp

                def run_select(plan, store):
                    return _lp(plan, store)
                """,
        }
        src = textwrap.dedent(files["frontend/session.py"])
        assert not self.GREP.search(src)  # grep only saw lower_plan(
        found = lint_fixture(tmp_path, files, ["serving-cache"])
        assert [f.rule for f in found] == ["serving-cache"]

    def test_serving_plane_itself_quiet(self, tmp_path):
        files = {
            "batch/lower.py": "def lower_plan(plan, store):\n    pass\n",
            "frontend/serving.py": """
                from ..batch.lower import lower_plan

                def execute(plan, store):
                    return lower_plan(plan, store)
                """,
            "frontend/session.py": '''
                """Selects lower via serving, never lower_plan( direct."""

                def run_select(serving, plan):
                    return serving.execute(plan)
                ''',
        }
        assert lint_fixture(tmp_path, files, ["serving-cache"]) == []


class TestBoundaryIO:
    GREP = re.compile(r"LocalFsObjectStore\(")

    def test_alias_caught_where_grep_missed(self, tmp_path):
        files = {
            "storage/object_store.py": """
                class LocalFsObjectStore:
                    def __init__(self, root):
                        self.root = root

                def open_object_store(root):
                    return LocalFsObjectStore(root)
                """,
            "worker/rogue.py": """
                from ..storage.object_store import LocalFsObjectStore as FS

                def open_raw(root):
                    return FS(root)
                """,
        }
        src = textwrap.dedent(files["worker/rogue.py"])
        assert not self.GREP.search(src)
        found = lint_fixture(tmp_path, files, ["boundary-io"])
        assert [f.rule for f in found] == ["boundary-io"]

    def test_docstring_and_wrapped_open_quiet(self, tmp_path):
        files = {
            "storage/object_store.py": """
                class LocalFsObjectStore:
                    def __init__(self, root):
                        self.root = root

                def open_object_store(root):
                    return LocalFsObjectStore(root)
                """,
            "worker/clean.py": '''
                """Never LocalFsObjectStore(...) — open_object_store."""
                from ..storage.object_store import open_object_store

                def open_ok(root):
                    return open_object_store(root)
                ''',
        }
        src = textwrap.dedent(files["worker/clean.py"])
        assert self.GREP.search(src)
        assert lint_fixture(tmp_path, files, ["boundary-io"]) == []


class TestMetaBoundary:
    GREP = re.compile(r"FileMetaStore\(")

    META_STUB = {
        "meta/store.py": """
            class FileMetaStore:
                def __init__(self, root):
                    self.root = root
            """,
        "meta/service.py": """
            from .store import FileMetaStore

            class MetaService:
                def __init__(self, root):
                    self.store = FileMetaStore(root)
            """,
    }

    def test_alias_caught_where_grep_missed(self, tmp_path):
        files = dict(self.META_STUB)
        files["frontend/rogue.py"] = """
            from ..meta.store import FileMetaStore as MS

            def open_raw(root):
                return MS(root)
            """
        src = textwrap.dedent(files["frontend/rogue.py"])
        assert not self.GREP.search(src)
        found = lint_fixture(tmp_path, files, ["meta-boundary"])
        assert [f.rule for f in found] == ["meta-boundary"]

    def test_meta_internal_and_docstring_quiet(self, tmp_path):
        files = dict(self.META_STUB)
        files["frontend/clean.py"] = '''
            """Never FileMetaStore(...) — go through MetaService."""
            from ..meta.service import MetaService

            def attach(root):
                return MetaService(root)
            '''
        src = textwrap.dedent(files["frontend/clean.py"])
        assert self.GREP.search(src)
        assert lint_fixture(tmp_path, files, ["meta-boundary"]) == []


FUSED_FIXTURE_PRELUDE = """
    import jax

    def agg_epoch_body(chunk_fn, core):
        def epoch(state, k):
            state = core.apply_chunk(state, k)
            {body_line}
            return state
        return epoch

    def fused_source_agg_epoch(chunk_fn, core):
        epoch = agg_epoch_body(chunk_fn, core)
        return jax.jit(epoch, static_argnums=(1,))

    EPOCH_BUILDERS = {{"source_agg": fused_source_agg_epoch}}
    """


class TestDispatchDiscipline:
    def _files(self, body_line, core_body="return state"):
        return {
            "ops/fused_epoch.py": FUSED_FIXTURE_PRELUDE.format(
                body_line=body_line),
            "ops/core.py": f"""
                class AggCore:
                    def apply_chunk(self, state, k):
                        {core_body}
                """,
        }

    @pytest.mark.parametrize("bad,needle", [
        ("state = jax.device_get(state)", "device_get"),
        ("jax.jit(lambda s: s)", "nested"),
        ("state.block_until_ready()", "block_until_ready"),
        ("n = state.item()", "item"),
        ("n = int(state[0])", "int()"),
    ])
    def test_positive_inside_epoch_body(self, tmp_path, bad, needle):
        found = lint_fixture(tmp_path, self._files(bad),
                             ["dispatch-discipline"])
        assert found, bad
        assert all(f.rule == "dispatch-discipline" for f in found)
        assert any(needle in f.message for f in found)

    def test_positive_through_unknown_receiver_method(self, tmp_path):
        # core.apply_chunk is only resolvable by method-name fallback —
        # the closure must still reach the numpy materialization there
        files = self._files(
            "pass", core_body="import numpy as np\n"
                    "                        return np.asarray(state)")
        found = lint_fixture(tmp_path, files, ["dispatch-discipline"])
        assert any("asarray" in f.message and f.path == "ops/core.py"
                   for f in found)

    def test_negative_pure_epoch_and_host_side_transfer(self, tmp_path):
        files = self._files("state = state + k")
        # host-side checkpointing may device_get freely: not reachable
        # from any builder
        files["ops/snapshot.py"] = """
            import jax

            def snapshot_host(state):
                return jax.device_get(state)
            """
        assert lint_fixture(tmp_path, files,
                            ["dispatch-discipline"]) == []

    def test_builders_own_jit_is_legitimate(self, tmp_path):
        # the ONE jax.jit in the builder body itself must not count as
        # nested
        files = self._files("state = state * 2")
        found = lint_fixture(tmp_path, files, ["dispatch-discipline"])
        assert found == []

    def test_lax_scan_body_is_a_root(self, tmp_path):
        files = {"ops/scanner.py": """
            import jax

            def run(xs):
                def body(carry, x):
                    carry = carry + jax.device_get(x)
                    return carry, x
                return jax.lax.scan(body, 0, xs)
            """}
        found = lint_fixture(tmp_path, files, ["dispatch-discipline"])
        assert len(found) == 1 and "device_get" in found[0].message


class TestDispatchCoverage:
    def test_static_roots_equal_runtime_registries(self):
        """The acceptance contract: the rule provably covers every
        function reachable from the registries. The static parse of the
        registry dicts must see exactly the entries the imported dicts
        hold, and each builder's closure must reach its epoch body and
        the device cores it dispatches into."""
        from risingwave_tpu.ops.fused_epoch import EPOCH_BUILDERS
        from risingwave_tpu.ops.fused_sharded import \
            SHARDED_EPOCH_BUILDERS
        from risingwave_tpu.analysis.rules_purity import \
            DispatchDiscipline
        pkg = load_package(package_root())
        cov = DispatchDiscipline().coverage(pkg)
        assert set(cov["EPOCH_BUILDERS"]) == set(EPOCH_BUILDERS)
        assert set(cov["SHARDED_EPOCH_BUILDERS"]) == \
            set(SHARDED_EPOCH_BUILDERS)
        for reg in ("EPOCH_BUILDERS", "SHARDED_EPOCH_BUILDERS"):
            for key, reach in cov[reg].items():
                # every builder's closure reaches its epoch body (named
                # "...epoch": the solo/sharded builders' <locals>.epoch,
                # the group builder's sharded_coscheduled_epoch)
                assert any(q.rsplit(".", 1)[-1].endswith("epoch")
                           for q in reach), (reg, key)
                assert len(reach) >= 5, (reg, key)
        everything = {q for d in cov.values() for v in d.values()
                      for q in v}
        for probe in ("ops.hash_table", "ops.session_window",
                      "ops.stream_q3", "ops.interval_join",
                      "parallel.sharded_agg.shard_map_compat"):
            assert any(probe in q for q in everything), probe


class TestTracePurity:
    def test_wall_clock_in_jitted_function(self, tmp_path):
        files = {"ops/impure.py": """
            import time

            import jax

            @jax.jit
            def stamp(x):
                return x + time.time()
            """}
        found = lint_fixture(tmp_path, files, ["trace-purity"])
        assert len(found) == 1 and "time.time" in found[0].message

    def test_host_rng_in_wrapped_function(self, tmp_path):
        files = {"ops/impure.py": """
            import random

            import jax

            def jitter(x):
                return x + random.random()

            jitter_v = jax.vmap(jitter)
            """}
        found = lint_fixture(tmp_path, files, ["trace-purity"])
        assert len(found) == 1 and "random.random" in found[0].message

    def test_mutable_default_on_traced_function(self, tmp_path):
        files = {"ops/impure.py": """
            import jax

            @jax.jit
            def accum(x, seen=[]):
                return x
            """}
        found = lint_fixture(tmp_path, files, ["trace-purity"])
        assert len(found) == 1 and "mutable default" in found[0].message

    def test_partial_jit_decorator_is_a_root(self, tmp_path):
        files = {"ops/impure.py": """
            import functools
            import time

            import jax

            @functools.partial(jax.jit, static_argnames=("k",))
            def stamp(x, k):
                return x + time.time()
            """}
        found = lint_fixture(tmp_path, files, ["trace-purity"])
        assert len(found) == 1 and "time.time" in found[0].message

    def test_pallas_kernel_is_a_root(self, tmp_path):
        files = {"ops/kernel.py": """
            import random

            from jax.experimental import pallas as pl

            def _kernel(x_ref, o_ref):
                o_ref[...] = x_ref[...] * random.random()

            def run(x):
                return pl.pallas_call(_kernel,
                                      out_shape=x)(x)
            """}
        found = lint_fixture(tmp_path, files, ["trace-purity"])
        assert len(found) == 1 and "random.random" in found[0].message

    def test_jax_random_and_untraced_clock_are_fine(self, tmp_path):
        files = {"ops/pure.py": """
            import time

            import jax

            @jax.jit
            def step(state, key):
                return state + jax.random.uniform(key)

            def host_metrics():
                return time.time()
            """}
        assert lint_fixture(tmp_path, files, ["trace-purity"]) == []


SESSION_HEADER = """
    class Session:
        def __init__(self):
            self._data_version = 0
            self._mutation_depth = 0

        def _enter_mutation(self):
            self._mutation_depth += 1
            if self._mutation_depth == 1:
                self._data_version += 1

        def _exit_mutation(self):
            self._mutation_depth -= 1
            if self._mutation_depth == 0:
                self._data_version += 1
    """


class TestSeqlockDiscipline:
    def test_direct_version_write_flagged(self, tmp_path):
        files = {"frontend/session.py": SESSION_HEADER + """
            def sneak(self):
                self._data_version += 2
        """}
        found = lint_fixture(tmp_path, files, ["seqlock-discipline"])
        assert len(found) == 1 and "_data_version" in found[0].message

    def test_enter_without_finally_exit_flagged(self, tmp_path):
        files = {"frontend/session.py": SESSION_HEADER + """
            def tick(self):
                self._enter_mutation()
                work = 1
                self._exit_mutation()
                return work
        """}
        found = lint_fixture(tmp_path, files, ["seqlock-discipline"])
        assert len(found) == 1 and "finally" in found[0].message

    def test_bracketed_mutator_is_clean(self, tmp_path):
        files = {"frontend/session.py": SESSION_HEADER + """
            def tick(self):
                self._enter_mutation()
                try:
                    return 1
                finally:
                    self._exit_mutation()
        """}
        assert lint_fixture(tmp_path, files, ["seqlock-discipline"]) == []

    def test_enter_inside_try_body_is_clean(self, tmp_path):
        files = {"frontend/session.py": SESSION_HEADER + """
            def tick(self):
                try:
                    self._enter_mutation()
                    return 1
                finally:
                    self._exit_mutation()
        """}
        assert lint_fixture(tmp_path, files, ["seqlock-discipline"]) == []

    def test_balanced_counts_do_not_launder_unprotected_enter(
            self, tmp_path):
        # enters=1, exits=1, one exit in a finally — a per-function
        # COUNT check calls this clean, but the finally belongs to an
        # unrelated try: an exception after the enter leaves
        # _data_version odd forever. The check must be structural.
        files = {"frontend/session.py": SESSION_HEADER + """
            def tick(self):
                try:
                    prep = 1
                finally:
                    self._exit_mutation()
                self._enter_mutation()
                work = 2
                return work
        """}
        found = lint_fixture(tmp_path, files, ["seqlock-discipline"])
        assert len(found) == 1 and "finally" in found[0].message

    def test_foreign_module_write_flagged(self, tmp_path):
        files = {
            "frontend/session.py": SESSION_HEADER,
            "frontend/serving.py": """
                def corrupt(session):
                    session._data_version += 1
                """,
        }
        found = lint_fixture(tmp_path, files, ["seqlock-discipline"])
        assert len(found) == 1 and found[0].path == "frontend/serving.py"


FAILPOINT_STUB = """
    DECLARED_SITES = frozenset({{{sites}}})
    KNOWN_SITES = set(DECLARED_SITES)

    def fail_point(name):
        pass
    """


class TestFailpointHonesty:
    def _files(self, sites, caller_lines):
        body = "".join(f"    {line}\n" for line in caller_lines)
        return {
            "common/failpoint.py": FAILPOINT_STUB.format(sites=sites),
            "storage/io.py":
                "from ..common.failpoint import fail_point\n\n"
                "def write(b):\n" + body,
        }

    def test_declared_equals_executed_is_clean(self, tmp_path):
        files = self._files('"sst.write"',
                            ['fail_point("sst.write")'])
        assert lint_fixture(tmp_path, files, ["failpoint-honesty"]) == []

    def test_undeclared_site_flagged_at_call(self, tmp_path):
        files = self._files('"sst.write"',
                            ['fail_point("sst.write")',
                             'fail_point("sst.rogue")'])
        found = lint_fixture(tmp_path, files, ["failpoint-honesty"])
        msgs = [f.message for f in found]
        assert any("sst.rogue" in m and "not in DECLARED" in m
                   for m in msgs)
        assert any(f.path == "storage/io.py" for f in found)

    def test_stale_declared_site_flagged(self, tmp_path):
        files = self._files('"sst.write", "never.hit"',
                            ['fail_point("sst.write")'])
        found = lint_fixture(tmp_path, files, ["failpoint-honesty"])
        assert len(found) == 1
        assert "never.hit" in found[0].message
        assert found[0].path == "common/failpoint.py"

    def test_dynamic_site_name_flagged(self, tmp_path):
        files = self._files('"sst.write"',
                            ['site = "sst" + ".write"',
                             'fail_point(site)',
                             'fail_point("sst.write")'])
        found = lint_fixture(tmp_path, files, ["failpoint-honesty"])
        assert len(found) == 1 and "non-literal" in found[0].message

    def test_keyword_call_counts_as_executed(self, tmp_path):
        # fail_point(name="x") must satisfy the declared site, not be
        # reported as a stale registry entry
        files = self._files('"sst.write"',
                            ['fail_point(name="sst.write")'])
        assert lint_fixture(tmp_path, files, ["failpoint-honesty"]) == []

    def test_undeclared_keyword_site_flagged(self, tmp_path):
        files = self._files('"sst.write"',
                            ['fail_point("sst.write")',
                             'fail_point(name="sst.rogue")'])
        found = lint_fixture(tmp_path, files, ["failpoint-honesty"])
        assert any("sst.rogue" in f.message and "not in DECLARED"
                   in f.message for f in found)


class TestRootNameNormalisation:
    def test_foreign_root_dir_name_still_enforced(self, tmp_path):
        """Rule targets are written against the canonical package name;
        a tree rooted at any other directory name (fixture copy,
        vendored checkout) must lint identically — a mismatched root
        must not silently disable every boundary rule."""
        root = tmp_path / "pkgcopy"
        files = dict(DISPATCH_STUB)
        files["worker/rogue.py"] = """
            from ..stream.dispatch import PermitChannel as PC

            def wire():
                return PC(4)
            """
        for rel, src in files.items():
            p = root / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(src))
        findings, _, _ = lint_package(root, [RULES["exchange-boundary"]])
        assert len(findings) == 1
        assert findings[0].path == "worker/rogue.py"


class TestSyncFetchDiscipline:
    """sync-fetch-discipline: blocking device fetches on the tick path
    (Session._tick_impl + the fused engines' per-tick methods) must go
    through common/fetch.py (PR 14, docs/performance.md "Pipelined
    tick")."""

    FETCH_STUB = {
        "common/fetch.py": """
            import jax

            class FetchFuture:
                def __init__(self, tree, dispatch=None):
                    self._tree = tree

                def result(self):
                    return jax.device_get(self._tree)

            def async_fetch(tree, dispatch=None):
                return FetchFuture(tree)

            def fetch(tree, dispatch=None):
                return FetchFuture(tree).result()
            """,
    }

    def test_blocking_fetch_in_engine_flush_flagged(self, tmp_path):
        files = dict(self.FETCH_STUB)
        files["stream/coschedule.py"] = """
            import jax
            import numpy as np

            class CoGroup:
                def flush(self):
                    packed, ranks = self._probe(self.stacked)
                    return np.asarray(jax.device_get(packed))
            """
        found = lint_fixture(tmp_path, files, ["sync-fetch-discipline"])
        assert [f.rule for f in found] == ["sync-fetch-discipline"]
        assert found[0].path == "stream/coschedule.py"
        assert "device_get" in found[0].message

    def test_closure_from_tick_impl_through_helper_flagged(self, tmp_path):
        # the blocking fetch hides one helper away from the tick driver:
        # reachability (not per-line grep) must find it
        files = dict(self.FETCH_STUB)
        files["frontend/session.py"] = """
            import jax

            def _decode_stats(packed):
                return jax.device_get(packed)

            class Session:
                def _feed(self, epoch):
                    return _decode_stats(self._probe())

                def _tick_impl(self, generate):
                    self._fused.tick(1, False, generate)
                    return self._feed(1)
            """
        # the registry's driver is a root of its own: the callgraph
        # cannot type ``self._fused``
        files["stream/fused_jobs.py"] = """
            import jax

            class FusedJobs:
                def _push(self, outs):
                    return jax.device_get(outs)

                def tick(self, epoch, checkpoint, generate):
                    return self._push(self._groups())
            """
        found = lint_fixture(tmp_path, files, ["sync-fetch-discipline"])
        assert [f.path for f in found] == ["frontend/session.py",
                                           "stream/fused_jobs.py"]
        assert "_decode_stats" in found[0].message
        assert "FusedJobs._push" in found[1].message

    def test_block_until_ready_and_device_attr_asarray_flagged(
            self, tmp_path):
        files = dict(self.FETCH_STUB)
        files["parallel/fused.py"] = """
            import jax
            import numpy as np

            class ShardedCoGroup:
                def run_epoch(self, k):
                    jax.block_until_ready(self.stacked)

                def _settle(self):
                    return np.asarray(self._rovf)
            """
        found = lint_fixture(tmp_path, files, ["sync-fetch-discipline"])
        assert sorted(("block_until_ready" in f.message,
                       "asarray" in f.message)
                      for f in found) == [(False, True), (True, False)]

    def test_through_fetch_helper_is_clean(self, tmp_path):
        # the refactored shape: async_fetch at dispatch time, result()
        # at flush time — the helper module's own device_get is the one
        # blessed crossing and stays exempt
        files = dict(self.FETCH_STUB)
        files["stream/coschedule.py"] = """
            import numpy as np

            from ..common.fetch import async_fetch

            class CoGroup:
                def begin_flush(self):
                    packed, ranks = self._probe(self.stacked)
                    self.pending = async_fetch(packed)

                def finish_flush(self):
                    return np.asarray(self.pending.result())
            """
        assert lint_fixture(tmp_path, files,
                            ["sync-fetch-discipline"]) == []

    def test_non_tick_methods_stay_out_of_scope(self, tmp_path):
        # checkpoint/debug surfaces (export_host, merged_group_values)
        # legitimately materialize host copies — not per-tick work
        files = dict(self.FETCH_STUB)
        files["parallel/fused.py"] = """
            import jax

            class ShardedFusedAgg:
                def export_host(self):
                    return jax.device_get(self.stacked)

                def merged_group_values(self):
                    return jax.device_get(self.stacked)
            """
        assert lint_fixture(tmp_path, files,
                            ["sync-fetch-discipline"]) == []

    def test_real_package_has_exactly_one_reasoned_drain_allow(self):
        """The real tree keeps ONE deliberately blocking fetch — the
        sharded grow-retry drain — behind a reasoned allow pragma; the
        rule must see it raw and the driver must suppress it."""
        from risingwave_tpu.analysis.core import RULES as _R
        pkg = load_package(package_root())
        raw = list(_R["sync-fetch-discipline"].check(pkg))
        assert [f.path for f in raw] == ["parallel/fused.py"], \
            [f.render() for f in raw]
        findings, _, _ = lint_package(
            package_root(), [_R["sync-fetch-discipline"]])
        assert findings == []


class TestSuppressions:
    def test_allow_with_reason_suppresses(self, tmp_path):
        files = dict(DISPATCH_STUB)
        files["worker/rogue.py"] = """
            from ..stream.dispatch import PermitChannel as PC

            def wire():
                return PC(4)  # rwlint: allow(exchange-boundary): test harness channel, not a data path
            """
        assert lint_fixture(tmp_path, files, ["exchange-boundary"]) == []

    def test_allow_without_reason_is_itself_a_finding(self, tmp_path):
        files = dict(DISPATCH_STUB)
        files["worker/rogue.py"] = """
            from ..stream.dispatch import PermitChannel as PC

            def wire():
                return PC(4)  # rwlint: allow(exchange-boundary)
            """
        found = lint_fixture(tmp_path, files, ["exchange-boundary"])
        rules = sorted(f.rule for f in found)
        assert rules == ["exchange-boundary", "pragma"]

    def test_pragma_on_preceding_comment_line(self, tmp_path):
        files = dict(DISPATCH_STUB)
        files["worker/rogue.py"] = """
            from ..stream.dispatch import PermitChannel as PC

            def wire():
                # rwlint: allow(exchange-boundary): fixture exercises the pragma-above form
                return PC(4)
            """
        assert lint_fixture(tmp_path, files, ["exchange-boundary"]) == []


UDF_STUB = {
    "udf/__init__.py": "",
    "udf/runtime.py": """
        def eval_udf_batch(spec, datas, masks):
            return spec.fn(*datas)
        """,
    "udf/registry.py": """
        UDF_SPECS = {}

        def get_udf(name):
            return UDF_SPECS[name]
        """,
}


class TestUdfBoundary:
    def test_direct_eval_in_tick_module_caught(self, tmp_path):
        files = dict(UDF_STUB)
        files["stream/rogue.py"] = """
            from ..udf.runtime import eval_udf_batch as ev

            def on_chunk(spec, datas, masks):
                return ev(spec, datas, masks)
            """
        found = lint_fixture(tmp_path, files, ["udf-boundary"])
        assert [f.rule for f in found] == ["udf-boundary"]
        assert found[0].path == "stream/rogue.py"

    def test_server_side_eval_exempt(self, tmp_path):
        files = dict(UDF_STUB)
        files["udf/server.py"] = """
            from .runtime import eval_udf_batch

            def handle_call(spec, datas, masks):
                return eval_udf_batch(spec, datas, masks)
            """
        assert lint_fixture(tmp_path, files, ["udf-boundary"]) == []

    def test_registry_callable_grab_caught(self, tmp_path):
        files = dict(UDF_STUB)
        files["batch/rogue.py"] = """
            from ..udf.registry import UDF_SPECS, get_udf

            def fast_path(v):
                direct = get_udf("tax").fn(v)
                return direct + UDF_SPECS["tax"].fn(v)
            """
        found = lint_fixture(tmp_path, files, ["udf-boundary"])
        assert len(found) == 2
        assert all(f.path == "batch/rogue.py" for f in found)

    def test_docstring_mention_not_flagged(self, tmp_path):
        files = dict(UDF_STUB)
        files["stream/clean.py"] = '''
            """Never call eval_udf_batch(spec, ...) on the tick path."""

            def on_chunk(call_boundary, batch):
                return call_boundary(batch)
            '''
        assert lint_fixture(tmp_path, files, ["udf-boundary"]) == []

    def test_real_package_clean_with_exactly_one_reasoned_allow(self):
        """The shipped package carries exactly ONE udf-boundary allow —
        the client's opt-in inproc evaluator — and lints clean."""
        findings, counts, _ = lint_package(
            rules=[RULES["udf-boundary"]])
        assert counts["udf-boundary"] == 0, findings
        src = (package_root() / "udf" / "client.py").read_text()
        allows = [ln for ln in src.splitlines()
                  if "rwlint: allow(udf-boundary)" in ln]
        assert len(allows) == 1
        assert "inproc" in allows[0]    # the reason names the mode


class TestWiring:
    def test_package_lints_clean_within_budget(self):
        """Tier-1: the whole package is rwlint-clean, and the full run
        fits the <10 s CPU CI budget scripts/check.sh enforces."""
        t0 = time.monotonic()
        findings, counts, package = lint_package()
        elapsed = time.monotonic() - t0
        assert findings == [], "\n".join(f.render() for f in findings)
        assert len(package.modules) > 100
        assert set(counts) == {r.name for r in all_rules()}
        assert elapsed < 10.0, f"rwlint run took {elapsed:.1f}s"

    def test_json_output_shape(self):
        import json
        import subprocess
        import sys
        proc = subprocess.run(
            [sys.executable, "-m", "risingwave_tpu.analysis", "--json"],
            capture_output=True, text=True,
            cwd=str(package_root().parent))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["ok"] is True and doc["findings"] == []
        assert doc["files"] > 100 and doc["elapsed_s"] < 10.0
        assert set(doc["rules"]) == {r.name for r in all_rules()}

    def test_ci_mode_keeps_historical_ok_lines(self):
        import subprocess
        import sys
        proc = subprocess.run(
            [sys.executable, "-m", "risingwave_tpu.analysis", "--ci"],
            capture_output=True, text=True,
            cwd=str(package_root().parent))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        # the five migrated lints keep their exact check.sh OK lines
        for label in ("exchange-boundary", "wire-boundary",
                      "placement-mutation", "serving-cache",
                      "boundary-IO"):
            assert f"{label} lint: OK" in proc.stdout, label
