"""distlint (tools/lint): per-rule positive/negative fixtures, the
suppression and baseline machinery, the proto parser, and — the tier-1
gate — a full run over the real repo asserting zero non-baselined
findings (ISSUE 2 acceptance; docs/LINTS.md)."""

from __future__ import annotations

from pathlib import Path

from tools.lint import proto as protodef
from tools.lint import rules as rules_mod
from tools.lint.core import (
    RULES,
    apply_baseline,
    apply_suppressions,
    load_baseline,
    module_from_source,
    run_lint,
)
from tools.lint.rules import compare_wire_schema

REPO_ROOT = Path(__file__).resolve().parent.parent
PKG = "distributed_inference_server_tpu"


def check(rule: str, path: str, src: str):
    """Run one module-scope rule over fixture source, suppressions applied."""
    mod = module_from_source(path, src)
    findings = list(RULES[rule].check(mod))
    active, _ = apply_suppressions({path: mod}, findings)
    return active


# ---------------------------------------------------------------------------
# DL001 — blocking calls on async / serving-spine paths
# ---------------------------------------------------------------------------


def test_dl001_flags_sleep_in_async_def():
    out = check("DL001", f"{PKG}/serving/app.py", (
        "import time\n"
        "async def handler():\n"
        "    time.sleep(1)\n"
    ))
    assert [f.line for f in out] == [3]
    assert out[0].severity == "P0"


def test_dl001_flags_unawaited_event_wait_in_async_def():
    out = check("DL001", f"{PKG}/engine/x.py", (
        "async def f(ev):\n"
        "    ev.wait(5)\n"
    ))
    assert len(out) == 1


def test_dl001_flags_sync_sleep_on_serving_spine():
    out = check("DL001", f"{PKG}/serving/dispatcher.py", (
        "import time\n"
        "def loop():\n"
        "    time.sleep(0.01)\n"
    ))
    assert len(out) == 1 and out[0].severity == "P1"


def test_dl001_clean():
    # awaited sleep, Event.wait on a thread, sleep outside serving/
    assert not check("DL001", f"{PKG}/serving/app.py", (
        "import asyncio\n"
        "async def handler():\n"
        "    await asyncio.sleep(1)\n"
    ))
    assert not check("DL001", f"{PKG}/serving/dispatcher.py", (
        "def loop(self):\n"
        "    self._stop.wait(0.01)\n"
    ))
    assert not check("DL001", f"{PKG}/utils/profiler.py", (
        "import time\n"
        "def capture():\n"
        "    time.sleep(0.5)\n"
    ))


def test_dl001_suppression_comment():
    assert not check("DL001", f"{PKG}/serving/server.py", (
        "import time\n"
        "def drain():\n"
        "    time.sleep(0.05)  # distlint: ignore[DL001]\n"
    ))


# ---------------------------------------------------------------------------
# DL002 — guarded state mutated outside the lock
# ---------------------------------------------------------------------------

_DL002_POS = """
import threading
class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []
    def add(self, x):
        with self._lock:
            self._items.append(x)
    def racy(self, x):
        self._items.append(x)
"""


def test_dl002_flags_unlocked_mutation():
    out = check("DL002", f"{PKG}/serving/x.py", _DL002_POS)
    assert len(out) == 1
    assert out[0].context == "C.racy"
    assert "_items" in out[0].message


def test_dl002_clean_when_locked_and_for_locked_suffix():
    assert not check("DL002", f"{PKG}/serving/x.py", (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._items = []\n"
        "    def add(self, x):\n"
        "        with self._lock:\n"
        "            self._items.append(x)\n"
        "    def also_fine(self, x):\n"
        "        with self._lock:\n"
        "            self._items = [x]\n"
        # *_locked convention: caller holds the lock
        "    def _add_locked(self, x):\n"
        "        self._items.append(x)\n"
    ))


def test_dl002_ignores_classes_without_locks():
    assert not check("DL002", f"{PKG}/serving/x.py", (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._items = []\n"
        "    def add(self, x):\n"
        "        self._items.append(x)\n"
    ))


# ---------------------------------------------------------------------------
# DL003 — lock held across await / blocking call
# ---------------------------------------------------------------------------


def test_dl003_flags_sleep_under_lock():
    out = check("DL003", f"{PKG}/serving/x.py", (
        "import threading, time\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            time.sleep(1)\n"
    ))
    assert len(out) == 1 and out[0].severity == "P0"


def test_dl003_flags_await_under_lock():
    out = check("DL003", f"{PKG}/serving/x.py", (
        "async def f(self):\n"
        "    with self._lock:\n"
        "        await self.q.get()\n"
    ))
    assert len(out) == 1 and "await" in out[0].message


def test_dl003_condition_wait_on_held_lock_is_exempt():
    assert not check("DL003", f"{PKG}/serving/disagg.py", (
        "class C:\n"
        "    def worker(self):\n"
        "        with self._cv:\n"
        "            self._cv.wait(0.1)\n"
    ))


def test_dl003_other_objects_wait_under_lock_flagged():
    out = check("DL003", f"{PKG}/serving/x.py", (
        "class C:\n"
        "    def f(self):\n"
        "        with self._cv:\n"
        "            self._stop.wait(1.0)\n"
    ))
    assert len(out) == 1


# ---------------------------------------------------------------------------
# DL004 — silently swallowed broad excepts
# ---------------------------------------------------------------------------


def test_dl004_flags_silent_pass():
    out = check("DL004", f"{PKG}/serving/x.py", (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    ))
    assert len(out) == 1


def test_dl004_flags_bare_except():
    out = check("DL004", f"{PKG}/serving/x.py", (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        return None\n"
    ))
    assert len(out) == 1 and "bare except" in out[0].message


def test_dl004_clean_variants():
    # logging, metric increment, re-raise, and forwarding `e` all count
    assert not check("DL004", f"{PKG}/serving/x.py", (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        logger.exception('boom')\n"
    ))
    assert not check("DL004", f"{PKG}/serving/x.py", (
        "def f(self):\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        self.metrics.record_error('site')\n"
    ))
    assert not check("DL004", f"{PKG}/serving/x.py", (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        raise RuntimeError('wrapped')\n"
    ))
    assert not check("DL004", f"{PKG}/serving/x.py", (
        "def f(self, sink):\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:\n"
        "        sink.on_error(str(e), 'code')\n"
    ))
    # narrow excepts are out of scope
    assert not check("DL004", f"{PKG}/serving/x.py", (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except ValueError:\n"
        "        pass\n"
    ))


# ---------------------------------------------------------------------------
# DL005 — proto <-> protowire drift (pure comparator + parser)
# ---------------------------------------------------------------------------

_TOY_PROTO = """
syntax = "proto3";
package t;

enum Color {
  COLOR_UNSPECIFIED = 0;
  RED = 1;           // "red"
  DARK_BLUE = 2;     // "dark_blue"
}

message Outer {
  string name = 1;
  optional uint32 count = 2;
  repeated float vals = 3;
  Inner inner = 4;
  Color color = 5;
  message Inner {
    bytes data = 1;
  }
  oneof kind {
    Inner a = 6;
    string b = 7;
  }
}
"""

_TOY_MESSAGES = {
    "Outer": {
        1: ("name", "string", "one"),
        2: ("count", "uint32", "opt"),
        3: ("vals", "float", "rep"),
        4: ("inner", "msg:Outer.Inner", "opt"),
        5: ("color", "enum:Color", "one"),
        6: ("a", "msg:Outer.Inner", "opt"),
        7: ("b", "string", "opt"),
    },
    "Outer.Inner": {1: ("data", "bytes", "one")},
}
_TOY_ENUMS = {"Color": {1: "red", 2: "dark_blue"}}


def test_proto_parser_structure():
    schema = protodef.parse(_TOY_PROTO)
    assert set(schema.messages) == {"Outer", "Outer.Inner"}
    outer = schema.messages["Outer"]
    assert outer.fields[1].label == "one"
    assert outer.fields[2].label == "opt"
    assert outer.fields[3].label == "rep"
    assert outer.fields[6].label == "opt"  # oneof member
    assert schema.enums["Color"].values == {
        0: "COLOR_UNSPECIFIED", 1: "RED", 2: "DARK_BLUE"}
    kind, t = protodef.resolve_type(schema, "Outer", "Inner")
    assert (kind, t) == ("msg", "msg:Outer.Inner")


def test_dl005_clean_on_matching_tables():
    schema = protodef.parse(_TOY_PROTO)
    assert compare_wire_schema(schema, _TOY_MESSAGES, _TOY_ENUMS) == []


def test_dl005_detects_drift():
    schema = protodef.parse(_TOY_PROTO)
    # field number missing
    broken = {k: dict(v) for k, v in _TOY_MESSAGES.items()}
    del broken["Outer"][3]
    msgs = [m for _, m in compare_wire_schema(schema, broken, _TOY_ENUMS)]
    assert any("vals = 3" in m for m in msgs)
    # type drift
    broken = {k: dict(v) for k, v in _TOY_MESSAGES.items()}
    broken["Outer"][2] = ("count", "int64", "opt")
    msgs = [m for _, m in compare_wire_schema(schema, broken, _TOY_ENUMS)]
    assert any("type drift" in m for m in msgs)
    # cardinality drift
    broken = {k: dict(v) for k, v in _TOY_MESSAGES.items()}
    broken["Outer"][2] = ("count", "uint32", "one")
    msgs = [m for _, m in compare_wire_schema(schema, broken, _TOY_ENUMS)]
    assert any("cardinality drift" in m for m in msgs)
    # enum JSON-string drift
    msgs = [m for _, m in compare_wire_schema(
        schema, _TOY_MESSAGES, {"Color": {1: "red", 2: "blue"}})]
    assert any("JSON string drift" in m for m in msgs)


def test_dl005_flags_kvchunk_field_drift():
    """ISSUE 4 satellite: a drift in the streamed-handoff KvChunk table
    (type change, renumbered field, dropped crc) is caught against the
    real inference.proto — the varint would still decode, into the wrong
    thing, silently corrupting every streamed migration."""
    schema = protodef.parse_file(
        REPO_ROOT / PKG / "serving" / "inference.proto")
    messages, enums = rules_mod.load_protowire_tables(REPO_ROOT)
    broken = {k: dict(v) for k, v in messages.items()}
    broken["KvChunk"][6] = ("crc32", "int64", "one")  # type drift
    msgs = [m for a, m in compare_wire_schema(schema, broken, enums)
            if a == "KvChunk"]
    assert any("crc32" in m and "type drift" in m for m in msgs), msgs
    broken = {k: dict(v) for k, v in messages.items()}
    del broken["KvChunk"][7]  # payload dropped from the codec
    msgs = [m for a, m in compare_wire_schema(schema, broken, enums)
            if a == "KvChunk"]
    assert any("payload" in m for m in msgs), msgs
    broken = {k: dict(v) for k, v in messages.items()}
    # field 19 is unused in the real header (9 became total_chunks when
    # the fleet KV data plane extended it — ISSUE 13)
    broken["KvHandoffHeader"][19] = ("chunk_pages", "uint32", "one")
    msgs = [m for a, m in compare_wire_schema(schema, broken, enums)
            if a == "KvHandoffHeader"]
    assert any("not in inference.proto" in m for m in msgs), msgs


def test_dl005_real_schema_agrees():
    """The repo's actual proto and codec tables (also enforced by the
    project-scope rule inside the full run below; asserted directly here
    so a drift failure names this test)."""
    schema = protodef.parse_file(
        REPO_ROOT / PKG / "serving" / "inference.proto")
    messages, enums = rules_mod.load_protowire_tables(REPO_ROOT)
    assert compare_wire_schema(schema, messages, enums) == []


# ---------------------------------------------------------------------------
# DL006 — metric hygiene (synthetic collector + usage modules)
# ---------------------------------------------------------------------------

_METRICS_SRC = """
from prometheus_client import Counter, Gauge
class MetricsCollector:
    def __init__(self, registry=None):
        self.reqs = Counter("reqs_total", "requests", registry=registry)
        self.depth = Gauge("queue_depth", "depth", registry=registry)
        self.ghost = Counter("ghost_total", "never emitted",
                             registry=registry)
    def record_request(self):
        self.reqs.inc()
    def set_depth(self, n):
        self.depth.set(n)
    def dead_method(self):
        self.reqs.inc()
"""

_USER_SRC = """
class Handler:
    def __init__(self, metrics):
        self.metrics = metrics
    def handle(self):
        self.metrics.record_request()
    def update(self, n):
        self.metrics.set_depth(n)
"""


def _dl006(metrics_src, user_src):
    mpath = f"{PKG}/serving/metrics.py"
    mods = [module_from_source(mpath, metrics_src),
            module_from_source(f"{PKG}/serving/handler.py", user_src)]
    return list(RULES["DL006"].check_project(mods, REPO_ROOT))


def test_dl006_flags_unemitted_metric_and_dead_method():
    out = _dl006(_METRICS_SRC, _USER_SRC)
    msgs = [f.message for f in out]
    assert any("ghost" in m and "never emitted" in m for m in msgs)
    assert any("dead_method" in m for m in msgs)
    assert len(out) == 2


def test_dl006_flags_typoed_emission_site():
    out = _dl006(_METRICS_SRC, _USER_SRC.replace(
        "record_request()", "record_requests()"))
    assert any("record_requests" in f.message and "does not exist"
               in f.message for f in out)


def test_dl006_clean():
    clean_metrics = _METRICS_SRC.replace(
        """        self.ghost = Counter("ghost_total", "never emitted",
                             registry=registry)
""", "").replace("""    def dead_method(self):
        self.reqs.inc()
""", "")
    assert _dl006(clean_metrics, _USER_SRC) == []


def test_dl006_flags_duplicate_prometheus_name():
    dup = _METRICS_SRC.replace('Gauge("queue_depth"', 'Gauge("reqs_total"')
    out = _dl006(dup, _USER_SRC)
    assert any("duplicate prometheus metric name" in f.message for f in out)


# ---------------------------------------------------------------------------
# DL007 — device work in the per-token decode loop
# ---------------------------------------------------------------------------


def test_dl007_flags_jnp_in_hot_function():
    out = check("DL007", f"{PKG}/engine/engine.py", (
        "import jax.numpy as jnp\n"
        "class LLMEngine:\n"
        "    def _emit_token(self, seq, t, outputs):\n"
        "        pad = jnp.zeros((4,))\n"
        "        return pad\n"
    ))
    assert len(out) == 1 and out[0].severity == "P0"


def test_dl007_flags_host_sync_in_hot_function():
    out = check("DL007", f"{PKG}/engine/engine.py", (
        "class LLMEngine:\n"
        "    def _process_block(self, outputs):\n"
        "        x = self.arr.block_until_ready()\n"
        "        y = self.val.item()\n"
    ))
    assert len(out) == 2


def test_dl007_no_false_positive_on_double_buffered_export():
    """ISSUE 4 satellite: the streamed-handoff export machinery
    (export_handoff_pump / _finish and kv_cache's double-buffered pull
    loop) lives OUTSIDE the per-token hot set — np.asarray pulls and
    copy_to_host_async dispatches there are the intended design, and
    DL007 must not flag them. A genuinely hot-loop sync still needs an
    inline justification to pass (suppression round-trip below)."""
    assert not check("DL007", f"{PKG}/engine/engine.py", (
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "class LLMEngine:\n"
        "    def export_handoff_pump(self, session):\n"
        "        pending = self._pull(session.groups[0])\n"
        "        for n, group in enumerate(session.groups):\n"
        "            nxt = None\n"
        "            if n + 1 < len(session.groups):\n"
        "                nxt = self._pull(session.groups[n + 1])\n"
        "            hosts = [np.asarray(a) for a in pending]\n"
        "            session.chunks.append(self._encode(hosts))\n"
        "            pending = nxt\n"
        "    def _pull(self, group):\n"
        "        arrs = (self.state.k[:, jnp.asarray(group)],)\n"
        "        for a in arrs:\n"
        "            a.copy_to_host_async()\n"
        "        return arrs\n"
    ))
    # the same sync INSIDE a hot function is flagged, and an inline
    # justification suppresses it
    flagged = check("DL007", f"{PKG}/engine/engine.py", (
        "class LLMEngine:\n"
        "    def _process_block(self, outputs):\n"
        "        x = self.arr.item()\n"
    ))
    assert len(flagged) == 1
    assert not check("DL007", f"{PKG}/engine/engine.py", (
        "class LLMEngine:\n"
        "    def _process_block(self, outputs):\n"
        "        x = self.arr.item()  "
        "# distlint: ignore[DL007] — block boundary sync\n"
    ))


def test_dl007_mixed_step_reap_is_hot():
    """ISSUE 12 satellite: the mixed-step reap loop runs once per mixed
    dispatch and walks completed prompts through the emission path — it
    is policed exactly like the decode loop (no device work / host sync
    beyond the one np.asarray block-boundary read)."""
    out = check("DL007", f"{PKG}/engine/engine.py", (
        "import jax.numpy as jnp\n"
        "class LLMEngine:\n"
        "    def _reap_mixed_prefill(self, group, chunk_lens, p_toks,\n"
        "                            p_lps, outputs):\n"
        "        pad = jnp.zeros((4,))\n"
        "        x = self.arr.item()\n"
        "        return pad, x\n"
    ))
    assert len(out) == 2 and all(f.severity == "P0" for f in out)
    # the block-boundary np.asarray read is the intended design
    assert not check("DL007", f"{PKG}/engine/engine.py", (
        "import numpy as np\n"
        "class LLMEngine:\n"
        "    def _reap_mixed_prefill(self, group, chunk_lens, p_toks,\n"
        "                            p_lps, outputs):\n"
        "        toks = np.asarray(p_toks)\n"
        "        return toks\n"
    ))
    # the mixed LAUNCH function is NOT hot: its jnp uploads are the
    # per-dispatch design, like _launch's
    assert not check("DL007", f"{PKG}/engine/engine.py", (
        "import jax.numpy as jnp\n"
        "class LLMEngine:\n"
        "    def _mixed_step(self, outputs):\n"
        "        return jnp.zeros((4,))\n"
    ))


def test_dl007_clean():
    # numpy host work in hot functions is fine; jnp outside them is fine
    assert not check("DL007", f"{PKG}/engine/engine.py", (
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "class LLMEngine:\n"
        "    def _process_block(self, outputs):\n"
        "        toks = np.asarray(self.toks_d)\n"
        "    def _launch(self):\n"
        "        return jnp.zeros((4,))\n"
    ))
    # rule only applies to engine/engine.py
    assert not check("DL007", f"{PKG}/serving/x.py", (
        "import jax.numpy as jnp\n"
        "def _emit_token():\n"
        "    return jnp.zeros(1)\n"
    ))


# ---------------------------------------------------------------------------
# baseline machinery
# ---------------------------------------------------------------------------


def test_baseline_consumes_matching_findings():
    mod = module_from_source(f"{PKG}/serving/x.py", (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    ))
    findings = list(RULES["DL004"].check(mod))
    assert len(findings) == 1
    f = findings[0]
    entry = {"rule": f.rule, "path": f.path, "context": f.context,
             "line": f.line_text}
    new, matched, stale = apply_baseline(findings, [entry])
    assert new == [] and len(matched) == 1 and stale == []
    # a second identical finding needs a second entry (multiset consume)
    new, matched, _ = apply_baseline(findings * 2, [entry])
    assert len(new) == 1 and len(matched) == 1
    # stale entries surface for baseline shrinking
    _, _, stale = apply_baseline([], [entry])
    assert stale == [entry]


def test_baseline_match_survives_line_motion_but_not_edit():
    src = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    f0 = list(RULES["DL004"].check(
        module_from_source(f"{PKG}/serving/x.py", src)))[0]
    moved = list(RULES["DL004"].check(module_from_source(
        f"{PKG}/serving/x.py", "import os\n\n" + src)))[0]
    assert f0.key == moved.key and f0.line != moved.line
    edited = list(RULES["DL004"].check(module_from_source(
        f"{PKG}/serving/x.py", src.replace("def f", "def h"))))[0]
    assert f0.key != edited.key  # context changed -> re-triage


# ---------------------------------------------------------------------------
# the tier-1 gate: the real repo is clean
# ---------------------------------------------------------------------------


def test_repo_has_zero_nonbaselined_findings():
    """`python -m tools.lint.run` must exit 0: every finding is either
    fixed, suppressed inline with a justification, or grandfathered in
    tools/lint/baseline.json (which may only shrink — docs/LINTS.md)."""
    active, _suppressed = run_lint(REPO_ROOT)
    new, _matched, _stale = apply_baseline(active, load_baseline())
    assert new == [], "\n".join(f.render() for f in new)


def test_repo_p0_findings_are_never_baselined():
    """P0 severities (async blocking, lock-across-blocking, wire drift,
    hot-loop device work) must be fixed or suppressed-with-justification,
    not grandfathered."""
    baseline = load_baseline()
    p0_rules = {n for n, r in RULES.items() if r.severity == "P0"}
    offenders = [e for e in baseline if e.get("rule") in p0_rules]
    assert offenders == []


# ---------------------------------------------------------------------------
# DL008 — interprocedural thread-confinement (callgraph + threads layer)
# ---------------------------------------------------------------------------

from pathlib import Path as _Path  # noqa: E402

_NO_DOCS_ROOT = _Path("/nonexistent-distlint-fixture-root")


def pcheck(rule: str, sources, root=None):
    """Run one project-scope rule over fixture sources ({path: src}),
    suppressions applied."""
    mods = {p: module_from_source(p, s) for p, s in sources.items()}
    findings = list(RULES[rule].check_project(list(mods.values()),
                                              root or _NO_DOCS_ROOT))
    active, _ = apply_suppressions(mods, findings)
    return active


# modeled on the PR 5 `_fail_all_of`/`submit` double-resolve race: one
# attribute written by the spawned engine thread AND by submit(), which
# any other thread calls, with no common lock
_DL008_POS = """
import threading
class Runner:
    def __init__(self):
        self._inflight = {}
        self._thread = None
    def start(self):
        self._thread = threading.Thread(target=self._run, name="engine")
        self._thread.start()
    def submit(self, reqs):
        for r in reqs:
            self._inflight[r.request_id] = r
    def _run(self):
        while True:
            self._fail_all_of(list(self._inflight.values()))
    def _fail_all_of(self, reqs):
        for r in reqs:
            self._inflight.pop(r.request_id, None)
"""


def test_dl008_flags_double_resolve_write_pattern():
    out = pcheck("DL008", {f"{PKG}/serving/runner.py": _DL008_POS})
    assert len(out) == 1
    f = out[0]
    assert "_inflight" in f.message and "no common lock" in f.message
    assert "thread:engine" in f.message
    assert f.context == "Runner.submit"


def test_dl008_clean_with_common_lock_and_locked_convention():
    out = pcheck("DL008", {f"{PKG}/serving/runner.py": """
import threading
class Runner:
    def __init__(self):
        self._lock = threading.Lock()
        self._inflight = {}
        self._thread = None
    def start(self):
        self._thread = threading.Thread(target=self._run, name="engine")
        self._thread.start()
    def submit(self, reqs):
        with self._lock:
            for r in reqs:
                self._inflight[r.request_id] = r
    def _run(self):
        with self._lock:
            self._fail_all_locked()
    def _fail_all_locked(self):
        self._inflight.clear()
"""})
    assert out == []


def test_dl008_thread_confined_marker_and_suppression():
    src = """
import threading
class Engine:
    def __init__(self):
        self.state = {}
        self._thread = None
    def start(self):
        self._thread = threading.Thread(target=self._run)
        self._thread.start()
    def poke(self):
        self.state["x"] = 1
    def _run(self):
        self.state.clear()
"""
    assert len(pcheck("DL008", {f"{PKG}/engine/x.py": src})) == 1
    marked = src.replace("class Engine:",
                         "# distlint: thread-confined\nclass Engine:")
    assert pcheck("DL008", {f"{PKG}/engine/x.py": marked}) == []
    # inline suppression at the anchor write site also silences
    suppressed = src.replace(
        'self.state["x"] = 1',
        'self.state["x"] = 1  # distlint: ignore[DL008]')
    assert pcheck("DL008", {f"{PKG}/engine/x.py": suppressed}) == []


def test_dl008_threading_primitive_methods_exempt():
    out = pcheck("DL008", {f"{PKG}/serving/x.py": """
import threading
class C:
    def __init__(self):
        self._stop = threading.Event()
        self._thread = None
    def start(self):
        self._thread = threading.Thread(target=self._run)
        self._thread.start()
    def shutdown(self):
        self._stop.set()
    def reset(self):
        self._stop.clear()
    def _run(self):
        self._stop.clear()
"""})
    assert out == []


def test_dl008_site_suppression_does_not_mask_other_sites():
    """An ignore[DL008] on one write site waives exactly that site: a
    racy write of the same attribute elsewhere still flags (and the
    finding re-anchors there). The attribute-wide waiver is the ignore
    on the __init__ declaration."""
    src = """
import threading
class Runner:
    def __init__(self):
        self._inflight = {}
        self._thread = None
    def start(self):
        self._thread = threading.Thread(target=self._run, name="engine")
        self._thread.start()
    def submit(self, reqs):
        for r in reqs:
            self._inflight[r.request_id] = r  # distlint: ignore[DL008]
    def cancel_all(self):
        self._inflight.clear()
    def _run(self):
        self._inflight.clear()
"""
    out = pcheck("DL008", {f"{PKG}/serving/runner.py": src})
    assert len(out) == 1
    assert out[0].context == "Runner.cancel_all"  # re-anchored
    waived = src.replace(
        "self._inflight = {}",
        "self._inflight = {}  # distlint: ignore[DL008]")
    assert pcheck("DL008", {f"{PKG}/serving/runner.py": waived}) == []


def test_thread_root_marker_label_collision_stays_distinct():
    """A # distlint: thread-root marker whose label collides with an
    existing spawn root must NOT merge the two ownership domains — the
    race between them would silently disappear."""
    out = pcheck("DL008", {f"{PKG}/serving/x.py": """
import threading
class C:
    def __init__(self):
        self.jobs = {}
        self._thread = None
    def start(self, pool):
        self._thread = threading.Thread(target=self._run, name="pump")
        self._thread.start()
        pool.submit(self._drain)
    def _run(self):
        self.jobs["a"] = 1
    # distlint: thread-root[pump]
    def _drain(self):
        self.jobs.clear()
"""})
    assert len(out) == 1 and "jobs" in out[0].message


def test_spawn_root_fallback_labels_stay_distinct():
    """Two same-named classes in different modules spawning same-named
    threads must produce two distinct ownership roots — merging them
    would hide races between the two real threads."""
    from tools.lint import callgraph, threads

    src = """
import threading
class C:
    def __init__(self):
        self._thread = None
    def start(self):
        self._thread = threading.Thread(target=self._run, name="C._run")
        self._thread.start()
    def _run(self):
        pass
"""
    mods = [module_from_source(f"{PKG}/serving/{p}.py", src)
            for p in ("a", "b")]
    roots = threads.spawn_roots(callgraph.build_summary(mods))
    spawned = {label: fns for label, fns in roots.items()
               if label != "asyncio"}
    assert len(spawned) == 2
    assert all(len(fns) == 1 for fns in spawned.values())


def test_dl008_async_defs_are_a_thread_root():
    # an async handler (asyncio root) racing a spawned thread, no lock
    out = pcheck("DL008", {f"{PKG}/serving/x.py": """
import threading
class C:
    def __init__(self):
        self.pending = {}
        self._thread = None
    def start(self):
        self._thread = threading.Thread(target=self._drain)
        self._thread.start()
    async def handle(self, rid, req):
        self.pending[rid] = req
    def _drain(self):
        self.pending.clear()
"""})
    assert len(out) == 1 and "asyncio" in out[0].message


# ---------------------------------------------------------------------------
# DL009 — lock-order cycles
# ---------------------------------------------------------------------------


def test_dl009_flags_interprocedural_cycle():
    out = pcheck("DL009", {f"{PKG}/serving/x.py": """
import threading
class A:
    def __init__(self):
        self._lock_a = threading.Lock()
        self._lock_b = threading.Lock()
    def f(self):
        with self._lock_a:
            with self._lock_b:
                pass
    def g(self):
        with self._lock_b:
            self.helper()
    def helper(self):
        with self._lock_a:
            pass
"""})
    assert len(out) == 1
    assert "lock-order cycle" in out[0].message
    assert "A._lock_a" in out[0].message and "A._lock_b" in out[0].message


def test_dl009_clean_on_consistent_order():
    out = pcheck("DL009", {f"{PKG}/serving/x.py": """
import threading
class A:
    def __init__(self):
        self._lock_a = threading.Lock()
        self._lock_b = threading.Lock()
    def f(self):
        with self._lock_a:
            with self._lock_b:
                pass
    def g(self):
        with self._lock_a:
            self.helper()
    def helper(self):
        with self._lock_b:
            pass
"""})
    assert out == []


def test_dl009_plain_lock_reacquire_flagged_rlock_clean():
    src = """
import threading
class B:
    def __init__(self):
        self._lock = threading.{factory}()
    def outer(self):
        with self._lock:
            self.inner()
    def inner(self):
        with self._lock:
            pass
"""
    out = pcheck("DL009",
                 {f"{PKG}/serving/x.py": src.format(factory="Lock")})
    assert len(out) == 1 and "self-deadlock" in out[0].message
    assert pcheck("DL009",
                  {f"{PKG}/serving/x.py": src.format(factory="RLock")}) == []


# ---------------------------------------------------------------------------
# DL010 — internal-API call conformance
# ---------------------------------------------------------------------------

_TRACING_FIXTURE = """
import time
class Span:
    def set(self, **attrs):
        return self
    def event(self, name, **attrs):
        pass
    def context(self):
        return (self.trace_id, self.span_id)
class Tracer:
    def start(self, name, parent=None, **attributes):
        pass
    def finish(self, span, status="ok"):
        pass
"""

# the pre-structured-events signature (the PR 5 trap): kept as a fixture
# so DL010 provably still catches kwargs against a kwargs-less target
_TRACING_FIXTURE_LEGACY = _TRACING_FIXTURE.replace(
    "def event(self, name, **attrs):", "def event(self, name):")


def test_dl010_flags_pr5_span_event_kwargs_shape_on_legacy_signature():
    """The exact PR 5 bug: against the OLD no-kwargs ``Span.event``, a
    ``reason=`` kwarg is a runtime TypeError that turned an invisible
    redispatch into a client-visible failure — DL010 flags it."""
    out = pcheck("DL010", {
        f"{PKG}/utils/tracing.py": _TRACING_FIXTURE_LEGACY,
        f"{PKG}/serving/dispatcher.py": """
class Dispatcher:
    def redispatch(self, request, from_engine, reason):
        if request.span is not None:
            request.span.event("redispatched", reason=reason)
        return True
""",
    })
    assert len(out) == 1
    assert "unexpected keyword argument 'reason'" in out[0].message
    assert out[0].context == "Dispatcher.redispatch"
    assert out[0].severity == "P0"


def test_dl010_structured_event_attrs_conform():
    """Against the CURRENT ``Span.event(name, **attrs)`` signature the
    same kwargs shape is legal — and the old bare-name call shape still
    lints clean too (both shapes are live in the codebase)."""
    out = pcheck("DL010", {
        f"{PKG}/utils/tracing.py": _TRACING_FIXTURE,
        f"{PKG}/serving/dispatcher.py": """
class Dispatcher:
    def redispatch(self, request, from_engine, reason):
        if request.span is not None:
            request.span.event("redispatched", reason=reason)
            request.span.event("queued")
        return True
""",
    })
    assert out == []


def test_dl010_clean_conforming_span_calls():
    out = pcheck("DL010", {
        f"{PKG}/utils/tracing.py": _TRACING_FIXTURE,
        f"{PKG}/serving/dispatcher.py": """
class Dispatcher:
    def redispatch(self, request, from_engine, reason):
        if request.span is not None:
            request.span.set(redispatch_from=from_engine,
                             redispatch_reason=reason)
            request.span.event("redispatched")
        return True
""",
    })
    assert out == []


def test_dl010_flags_unknown_method_and_arity():
    out = pcheck("DL010", {
        f"{PKG}/utils/tracing.py": _TRACING_FIXTURE,
        f"{PKG}/serving/x.py": """
class H:
    def f(self, span):
        span.add_event("x")
        span.event("a", "b")
""",
    })
    msgs = sorted(f.message for f in out)
    assert any("no method 'add_event'" in m for m in msgs)
    assert any("takes 1 positional argument(s), got 2" in m for m in msgs)


def test_dl010_annotation_typed_receiver():
    # receiver typed via annotation, not named after the convention
    out = pcheck("DL010", {
        f"{PKG}/utils/tracing.py": _TRACING_FIXTURE,
        f"{PKG}/serving/x.py": f"""
from {PKG.replace('/', '.')}.utils.tracing import Span
class H:
    def f(self, s: Span):
        s.event("ok", 2)
""",
    })
    assert len(out) == 1 and "takes 1 positional" in out[0].message


def test_dl010_metrics_module_alias_members_not_flagged():
    out = pcheck("DL010", {
        f"{PKG}/serving/metrics.py": """
class EngineStatus:
    pass
class MetricsCollector:
    def record_error(self, site):
        pass
""",
        f"{PKG}/serving/x.py": f"""
from {PKG.replace('/', '.')}.serving import metrics
class H:
    def __init__(self, metrics):
        self.metrics = metrics
    def ok(self):
        self.metrics.record_error("site")
    def make(self):
        return metrics.EngineStatus()
""",
    })
    assert out == []


def test_dl010_faults_module_function_conformance():
    out = pcheck("DL010", {
        f"{PKG}/serving/faults.py": """
def fire(point):
    return False
def flag(point):
    return False
""",
        f"{PKG}/serving/x.py": f"""
from {PKG.replace('/', '.')}.serving import faults
def f():
    faults.fire("a.b", 3)
    faults.flagg("a.b")
""",
    })
    msgs = sorted(f.message for f in out)
    assert any("takes 1 positional argument(s), got 2" in m for m in msgs)
    assert any("no module-level 'flagg'" in m for m in msgs)


# ---------------------------------------------------------------------------
# DL011 — fault-point drift
# ---------------------------------------------------------------------------


def test_dl011_flags_unknown_point_against_real_catalog():
    out = pcheck("DL011", {f"{PKG}/serving/x.py": f"""
from {PKG.replace('/', '.')}.serving import faults
def f():
    faults.fire("bogus.point")
    faults.fire("runner.step")
"""}, root=REPO_ROOT)
    assert len(out) == 1
    assert "bogus.point" in out[0].message
    assert "RESILIENCE.md" in out[0].message


def test_dl011_spec_strings_and_fstrings_checked():
    out = pcheck("DL011", {f"{PKG}/serving/x.py": """
def scenarios(n):
    specs = ["bogus.crash:nth=1", f"runner.inbox:nth={n}"]
    return specs
"""}, root=REPO_ROOT)
    assert len(out) == 1 and "bogus.crash" in out[0].message


def test_dl011_multi_segment_points_supported_consistently():
    """All four point grammars accept dotted points of any depth — a
    catalog entry one regex can represent but another cannot would be a
    permanently unfixable finding."""
    faults_src = '''
"""Registry.

Point catalog:

``disagg.chunk.late``  three segments, fired below
"""
def fire(point):
    return False
'''
    out = pcheck("DL011", {
        f"{PKG}/serving/faults.py": faults_src,
        f"{PKG}/serving/x.py": f"""
from {PKG.replace('/', '.')}.serving import faults
def f():
    faults.fire("disagg.chunk.late")
    spec = "disagg.chunk.late:nth=1"
    return spec
""",
    })
    assert out == []


def test_dl011_dead_catalog_entry_flagged():
    faults_src = '''
"""Fault registry.

Point catalog:

``a.b``     a live point
``dead.pt`` nobody fires this
"""
def fire(point):
    return False
'''
    out = pcheck("DL011", {
        f"{PKG}/serving/faults.py": faults_src,
        f"{PKG}/serving/x.py": f"""
from {PKG.replace('/', '.')}.serving import faults
def f():
    faults.fire("a.b")
""",
    })
    assert len(out) == 1
    assert "dead.pt" in out[0].message and "never fired" in out[0].message


# ---------------------------------------------------------------------------
# DL012 — config-key drift
# ---------------------------------------------------------------------------

_CONFIG_FIXTURE = f"{PKG}/serving/config.py"
_SCHEMA_SRC = """
_SCHEMA = {
    "server": {"port": (int, 8000), "host": (str, "0.0.0.0")},
    "queue": {"high_watermark": (int, 1000)},
}
"""


def test_dl012_flags_unknown_key_and_section():
    out = pcheck("DL012", {
        _CONFIG_FIXTURE: _SCHEMA_SRC + """
class ServerConfig:
    def get(self, section, key):
        return None
""",
        f"{PKG}/serving/x.py": f"""
from {PKG.replace('/', '.')}.serving.config import ServerConfig
def f(cfg: ServerConfig):
    a = cfg.get("server", "port")
    b = cfg.get("server", "bogus")
    c = cfg.get("sever", "port")
    d = {{}}.get("anything", "else")
    return a, b, c, d
""",
    })
    msgs = sorted(f.message for f in out)
    assert len(out) == 2
    assert any("server.bogus" in m for m in msgs)
    # receiver TYPED as ServerConfig -> unknown sections flag too
    assert any("unknown config section 'sever'" in m for m in msgs)


def test_dl012_config_named_dict_does_not_misfire():
    """A plain dict that happens to be named ``cfg`` (tokenizer JSON) is
    checked only when the section arg names a real section — and never
    for unknown sections."""
    out = pcheck("DL012", {
        _CONFIG_FIXTURE: _SCHEMA_SRC,
        f"{PKG}/models/x.py": """
def f(cfg):
    a = cfg.get("bos_token", "")
    b = cfg.get("sever", "port")
    c = cfg.get("server", "bogus")
    return a, b, c
""",
    })
    assert len(out) == 1 and "server.bogus" in out[0].message


def test_dl012_env_tokens_checked_everywhere():
    out = pcheck("DL012", {
        _CONFIG_FIXTURE: _SCHEMA_SRC,
        f"{PKG}/serving/x.py": """
import os
def f():
    ok = os.environ.get("DIS_TPU_SERVER__PORT")
    bad = os.environ.get("DIS_TPU_SERVER__PROT")
    other = os.environ.get("DIS_TPU_DEBUG_GATHER")
    return ok, bad, other
""",
    })
    assert len(out) == 1
    assert "DIS_TPU_SERVER__PROT" in out[0].message


def test_dl012_fleet_mesh_keys():
    """The KV-mesh knobs (config ``fleet.mesh_enabled`` /
    ``kv_rate_window_s`` / ``kv_rate_prior``) are schema keys like any
    other: correct accesses pass, a typo'd variant flags, and the env
    spellings resolve."""
    mesh_schema = """
_SCHEMA = {
    "fleet": {
        "mesh_enabled": (bool, False),
        "kv_rate_window_s": (float, 30.0),
        "kv_rate_prior": (float, 125000000.0),
    },
}
"""
    out = pcheck("DL012", {
        _CONFIG_FIXTURE: mesh_schema,
        f"{PKG}/serving/x.py": """
import os
def f(cfg):
    a = cfg.get("fleet", "mesh_enabled")
    b = cfg.get("fleet", "kv_rate_window_s")
    c = cfg.get("fleet", "kv_rate_prior")
    d = os.environ.get("DIS_TPU_FLEET__MESH_ENABLED")
    bad = cfg.get("fleet", "mesh_enable")
    return a, b, c, d, bad
""",
    })
    assert len(out) == 1 and "fleet.mesh_enable" in out[0].message


def test_dl012_fleet_ha_keys():
    """The registry-HA knobs (config ``fleet.registries`` / ``lease_s``
    / ``lease_suspect_s`` / ``standby_http``) are schema keys like any
    other: correct accesses pass, a typo'd variant flags, and the env
    spellings resolve."""
    ha_schema = """
_SCHEMA = {
    "fleet": {
        "registries": (tuple, []),
        "lease_s": (float, 3.0),
        "lease_suspect_s": (float, 1.5),
        "standby_http": (bool, True),
    },
}
"""
    out = pcheck("DL012", {
        _CONFIG_FIXTURE: ha_schema,
        f"{PKG}/serving/x.py": """
import os
def f(cfg):
    a = cfg.get("fleet", "registries")
    b = cfg.get("fleet", "lease_s")
    c = cfg.get("fleet", "lease_suspect_s")
    d = os.environ.get("DIS_TPU_FLEET__STANDBY_HTTP")
    bad = cfg.get("fleet", "lease_suspect")
    return a, b, c, d, bad
""",
    })
    assert len(out) == 1 and "fleet.lease_suspect" in out[0].message


def test_dl012_schema_internal_literals():
    out = pcheck("DL012", {_CONFIG_FIXTURE: _SCHEMA_SRC + """
HOT_RELOADABLE = {("server", "port"), ("queue", "high_watermrk")}
def validate(r):
    if r["server"]["prot"] <= 0:
        raise ValueError
"""})
    msgs = sorted(f.message for f in out)
    assert len(out) == 2
    assert any("queue.high_watermrk" in m for m in msgs)
    assert any("server.prot" in m for m in msgs)


# ---------------------------------------------------------------------------
# DL013 — span/event-name catalog drift
# ---------------------------------------------------------------------------

_DL013_CATALOG = """# Observability

| name | kind | emitted by |
|------|------|------------|
| `request.<endpoint>` | span | handler |
| `engine.infer` | span | runner |
| `queued` | event | handler |
| `admit` | timeline | recorder |
"""


def _dl013_root(tmp_path, catalog=_DL013_CATALOG):
    (tmp_path / "docs").mkdir(exist_ok=True)
    (tmp_path / "docs" / "OBSERVABILITY.md").write_text(catalog)
    return tmp_path


def test_dl013_flags_uncataloged_span_and_event(tmp_path):
    out = pcheck("DL013", {
        f"{PKG}/serving/x.py": """
class H:
    def go(self, span):
        s = self.tracer.start("mystery.span")
        span.event("mystery_event")
""",
    }, root=_dl013_root(tmp_path))
    emission = [f for f in out if f.path.endswith("x.py")]
    msgs = sorted(f.message for f in emission)
    assert any("'mystery.span'" in m for m in msgs)
    assert any("'mystery_event'" in m for m in msgs)
    assert len(emission) == 2
    # the unused catalog rows flag as dead entries, anchored in the doc
    assert all(f.path == "docs/OBSERVABILITY.md"
               for f in out if f not in emission)


def test_dl013_clean_and_fstring_head_matches_placeholder(tmp_path):
    out = pcheck("DL013", {
        f"{PKG}/serving/x.py": """
class H:
    def go(self, span, engine_span, endpoint):
        self.tracer.start(f"request.{endpoint}")
        with self.tracer.span("engine.infer"):
            pass
        span.event("queued")
        engine_span.event("queued")
""",
    }, root=_dl013_root(tmp_path))
    assert out == []


def test_dl013_dead_catalog_entry_flagged(tmp_path):
    out = pcheck("DL013", {
        f"{PKG}/serving/x.py": """
class H:
    def go(self, span, endpoint):
        self.tracer.start(f"request.{endpoint}")
        span.event("queued")
""",
    }, root=_dl013_root(tmp_path))
    assert len(out) == 1
    assert "never emitted" in out[0].message
    assert "'engine.infer'" in out[0].message
    assert out[0].path == "docs/OBSERVABILITY.md"


def test_dl013_timeline_rows_and_non_span_receivers_ignored(tmp_path):
    # `admit` is a kind=timeline row (documentation only) and calls on
    # non-span receivers (`recorder.note`, a random obj.event) are out
    # of scope — neither may produce findings
    out = pcheck("DL013", {
        f"{PKG}/serving/x.py": """
class H:
    def go(self, span, endpoint, recorder, widget):
        self.tracer.start(f"request.{endpoint}")
        self.tracer.span("engine.infer")
        span.event("queued")
        recorder.note("r1", "something_else")
        widget.event("not_a_span_event")
""",
    }, root=_dl013_root(tmp_path))
    assert out == []


def test_dl013_no_catalog_means_no_findings():
    # fixture roots without docs/OBSERVABILITY.md (every other pcheck
    # call in this file) must not explode or flag
    out = pcheck("DL013", {
        f"{PKG}/serving/x.py": """
class H:
    def go(self):
        self.tracer.start("anything.goes")
""",
    })
    assert out == []


def test_dl013_real_repo_catalog_is_in_sync():
    findings = list(RULES["DL013"].check_project(
        list(run_lint.__globals__["collect_modules"](REPO_ROOT).values()),
        REPO_ROOT,
    ))
    assert findings == [], [f.render() for f in findings]


def test_dl012_real_repo_schema_parses():
    from tools.lint.rules import DL012
    from tools.lint.core import collect_modules

    mods = collect_modules(REPO_ROOT,
                           files=[f"{PKG}/serving/config.py"])
    schema = DL012._parse_schema(mods[f"{PKG}/serving/config.py"])
    assert schema and "server" in schema and "port" in schema["server"]
    # ISSUE 12: the mixed-step knob is a real schema entry, so every
    # config.get("engine", "mixed_step_tokens") site is drift-checked
    assert "mixed_step_tokens" in schema["engine"]
    # ISSUE 13: the fleet KV data-plane knobs are real schema entries
    for key in ("kv_enabled", "kv_data_port", "kv_page_cost",
                "kv_max_streams", "kv_connect_timeout_s"):
        assert key in schema["fleet"], key
    # ISSUE 15: the gray-failure sections are real schema entries, so
    # every health.* / admission.* get site is drift-checked
    for key in ("enabled", "stall_s", "latency_ratio", "wire_failures",
                "breaker_open_s", "retry_budget_ratio", "slo_burn_high"):
        assert key in schema["health"], key
    for key in ("shed_enabled", "deadline_ms", "deadline_factor",
                "brownout", "retry_after_cap_s"):
        assert key in schema["admission"], key


def test_dl012_health_admission_keys_checked():
    """The gray-failure config keys (ISSUE 15, serving/health.py): a
    correct get (and the env-token spelling) is clean, typo'd keys in
    either new section flag."""
    out = pcheck("DL012", {
        _CONFIG_FIXTURE: """
_SCHEMA = {
    "health": {"stall_s": (float, 5.0), "wire_failures": (int, 3)},
    "admission": {"deadline_ms": (float, 0.0), "brownout": (bool, True)},
}
class ServerConfig:
    def get(self, section, key):
        return None
""",
        f"{PKG}/serving/x.py": f"""
import os
from {PKG.replace('/', '.')}.serving.config import ServerConfig
def f(cfg: ServerConfig):
    ok = cfg.get("health", "stall_s")
    ok2 = cfg.get("admission", "brownout")
    env = os.environ.get("DIS_TPU_HEALTH__WIRE_FAILURES")
    bad = cfg.get("health", "stall_seconds")
    bad2 = cfg.get("admission", "deadline_mss")
    return ok, ok2, env, bad, bad2
""",
    })
    assert len(out) == 2
    msgs = "\n".join(f.message for f in out)
    assert "health.stall_seconds" in msgs
    assert "admission.deadline_mss" in msgs


def test_dl012_mixed_step_key_checked():
    """The new engine.mixed_step_tokens key: a correct get is clean, a
    typo'd key flags against the schema."""
    out = pcheck("DL012", {
        _CONFIG_FIXTURE: """
_SCHEMA = {
    "engine": {"mixed_step_tokens": (int, 0), "max_batch": (int, 64)},
}
class ServerConfig:
    def get(self, section, key):
        return None
""",
        f"{PKG}/serving/x.py": f"""
from {PKG.replace('/', '.')}.serving.config import ServerConfig
def f(cfg: ServerConfig):
    ok = cfg.get("engine", "mixed_step_tokens")
    bad = cfg.get("engine", "mixed_step_tokenz")
    return ok, bad
""",
    })
    assert len(out) == 1
    assert "engine.mixed_step_tokenz" in out[0].message


def test_dl012_loop_keys_checked():
    """The kernel-looping knobs (config ``engine.loop_to_completion`` /
    ``engine.loop_max_steps``, ISSUE 19): correct gets and the env
    spelling are clean, a typo'd key flags against the schema."""
    out = pcheck("DL012", {
        _CONFIG_FIXTURE: """
_SCHEMA = {
    "engine": {
        "loop_to_completion": (bool, False),
        "loop_max_steps": (int, 256),
    },
}
class ServerConfig:
    def get(self, section, key):
        return None
""",
        f"{PKG}/serving/x.py": f"""
import os
from {PKG.replace('/', '.')}.serving.config import ServerConfig
def f(cfg: ServerConfig):
    ok = cfg.get("engine", "loop_to_completion")
    env = os.environ.get("DIS_TPU_ENGINE__LOOP_MAX_STEPS")
    bad = cfg.get("engine", "loop_max_stepz")
    return ok, env, bad
""",
    })
    assert len(out) == 1
    assert "engine.loop_max_stepz" in out[0].message


def test_dl012_fleet_kv_keys_checked():
    """The fleet.kv_* keys (ISSUE 13, serving/fleet_kv.py): a correct
    get (and the env-token spelling) is clean, a typo'd key flags."""
    out = pcheck("DL012", {
        _CONFIG_FIXTURE: """
_SCHEMA = {
    "fleet": {"kv_page_cost": (float, 0.6), "kv_max_streams": (int, 4)},
}
class ServerConfig:
    def get(self, section, key):
        return None
""",
        f"{PKG}/serving/x.py": f"""
import os
from {PKG.replace('/', '.')}.serving.config import ServerConfig
def f(cfg: ServerConfig):
    ok = cfg.get("fleet", "kv_page_cost")
    env = os.environ.get("DIS_TPU_FLEET__KV_MAX_STREAMS")
    bad = cfg.get("fleet", "kv_page_costs")
    return ok, env, bad
""",
    })
    assert len(out) == 1
    assert "fleet.kv_page_costs" in out[0].message


def test_dl012_latent_keys_checked():
    """The latent page codec knob (config ``cache.latent_rank``,
    ISSUE 20) and the extended quant values: a correct get (and the env
    spelling) is clean, a typo'd key flags against the schema."""
    out = pcheck("DL012", {
        _CONFIG_FIXTURE: """
_SCHEMA = {
    "cache": {"latent_rank": (int, 0), "host_tier_quant": (str, "none")},
    "disagg": {"wire_quant": (str, "none")},
}
class ServerConfig:
    def get(self, section, key):
        return None
""",
        f"{PKG}/serving/x.py": f"""
import os
from {PKG.replace('/', '.')}.serving.config import ServerConfig
def f(cfg: ServerConfig):
    ok = cfg.get("cache", "latent_rank")
    wq = cfg.get("disagg", "wire_quant")
    env = os.environ.get("DIS_TPU_CACHE__LATENT_RANK")
    bad = cfg.get("cache", "latent_rankz")
    return ok, wq, env, bad
""",
    })
    assert len(out) == 1
    assert "cache.latent_rankz" in out[0].message


# ---------------------------------------------------------------------------
# interprocedural infrastructure: targets, cache, CLI
# ---------------------------------------------------------------------------


def test_extra_targets_are_linted():
    from tools.lint.core import collect_modules

    mods = collect_modules(REPO_ROOT)
    assert "tools/chaos_fleet.py" in mods
    assert "tools/lint/callgraph.py" in mods
    assert "tools/lint/threads.py" in mods


def test_changed_files_filter_covers_extra_targets():
    from tools.lint.run import _is_lint_target

    assert _is_lint_target(f"{PKG}/serving/runner.py")
    assert _is_lint_target("tools/chaos_fleet.py")
    assert _is_lint_target("tools/lint/rules.py")
    assert not _is_lint_target("tests/test_distlint.py")
    assert not _is_lint_target("tools/soak_engine.py")
    assert not _is_lint_target("README.md")


def test_callgraph_build_is_memoized_and_keyed_on_content():
    from tools.lint import callgraph

    m1 = module_from_source(f"{PKG}/serving/a.py", "def f():\n    pass\n")
    s1 = callgraph.build_summary([m1])
    s2 = callgraph.build_summary([m1])
    assert s1 is s2  # in-process memo hit
    m2 = module_from_source(f"{PKG}/serving/a.py",
                            "def f():\n    return 1\n")
    assert callgraph.build_summary([m2]) is not s1  # content key changed


def test_github_format_emits_workflow_annotations(tmp_path, monkeypatch,
                                                  capsys):
    from tools.lint import run as run_mod

    (tmp_path / "pkg").mkdir()
    bad = tmp_path / "pkg" / "bad.py"
    bad.write_text(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    monkeypatch.setattr(run_mod, "REPO_ROOT", tmp_path)
    rc = run_mod.main(["--format=github", "--no-baseline",
                       "--rule", "DL004", "pkg/bad.py"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "::error file=pkg/bad.py,line=4,title=distlint DL004" in out


def test_interprocedural_rules_registered():
    for name in ("DL008", "DL009", "DL010", "DL011", "DL012", "DL013"):
        assert name in RULES
        assert RULES[name].scope == "project"


# ---------------------------------------------------------------------------
# DL014 — performance-telemetry catalog drift
# ---------------------------------------------------------------------------

_DL014_CATALOG = """# Observability

## Performance telemetry

| name | kind | meaning |
|------|------|---------|
| `engines` | perf-field | per-engine step clock |
| `windows` | perf-field | windowed stats |
| `slo_requests_total` | metric | verdict counts |
| `ttft_ms` | digest | windowed TTFT |
"""

_DL014_TELEDIGEST = '''
PERF_FIELDS = ("engines", "windows")
TELEMETRY_METRICS = ("slo_requests_total",)
DIGEST_NAMES = ("ttft_ms",)
'''

_DL014_METRICS = '''
from prometheus_client import Counter
class MetricsCollector:
    def __init__(self, r=None):
        self.slo_requests = Counter(
            "slo_requests_total", "d", ["tenant", "verdict"], registry=r)
'''


def _dl014_root(tmp_path, catalog=_DL014_CATALOG):
    (tmp_path / "docs").mkdir(exist_ok=True)
    (tmp_path / "docs" / "OBSERVABILITY.md").write_text(catalog)
    return tmp_path


def test_dl014_clean(tmp_path):
    out = pcheck("DL014", {
        f"{PKG}/serving/teledigest.py": _DL014_TELEDIGEST,
        f"{PKG}/serving/metrics.py": _DL014_METRICS,
    }, root=_dl014_root(tmp_path))
    assert out == []


def test_dl014_flags_undocumented_code_entry(tmp_path):
    out = pcheck("DL014", {
        f"{PKG}/serving/teledigest.py": _DL014_TELEDIGEST.replace(
            '("engines", "windows")', '("engines", "windows", "mystery")'
        ),
        f"{PKG}/serving/metrics.py": _DL014_METRICS,
    }, root=_dl014_root(tmp_path))
    assert len(out) == 1
    assert "'mystery'" in out[0].message
    assert out[0].path.endswith("teledigest.py")


def test_dl014_flags_dead_catalog_row(tmp_path):
    out = pcheck("DL014", {
        f"{PKG}/serving/teledigest.py": _DL014_TELEDIGEST,
        f"{PKG}/serving/metrics.py": _DL014_METRICS,
    }, root=_dl014_root(
        tmp_path,
        _DL014_CATALOG + "| `ghost_field` | perf-field | gone |\n"))
    assert len(out) == 1
    assert "'ghost_field'" in out[0].message
    assert out[0].path == "docs/OBSERVABILITY.md"


def test_dl014_flags_kind_disagreement(tmp_path):
    # cataloged as a digest, declared as a perf-field
    out = pcheck("DL014", {
        f"{PKG}/serving/teledigest.py": _DL014_TELEDIGEST.replace(
            'DIGEST_NAMES = ("ttft_ms",)',
            'DIGEST_NAMES = ()\nPERF_FIELDS2 = ()'
        ).replace('("engines", "windows")',
                  '("engines", "windows", "ttft_ms")'),
        f"{PKG}/serving/metrics.py": _DL014_METRICS,
    }, root=_dl014_root(tmp_path))
    assert any("catalogs disagree" in f.message for f in out)


def test_dl014_flags_unregistered_cataloged_metric(tmp_path):
    out = pcheck("DL014", {
        f"{PKG}/serving/teledigest.py": _DL014_TELEDIGEST,
        f"{PKG}/serving/metrics.py": (
            "class MetricsCollector:\n"
            "    pass\n"),
    }, root=_dl014_root(tmp_path))
    assert any("never registered" in f.message for f in out)


def test_dl014_no_teledigest_or_docs_means_no_findings(tmp_path):
    # fixture roots without the module or the catalog must not flag
    assert pcheck("DL014", {
        f"{PKG}/serving/metrics.py": _DL014_METRICS,
    }, root=_dl014_root(tmp_path)) == []
    assert pcheck("DL014", {
        f"{PKG}/serving/teledigest.py": _DL014_TELEDIGEST,
    }) == []


def test_dl014_real_repo_catalog_is_in_sync():
    findings = list(RULES["DL014"].check_project(
        list(run_lint.__globals__["collect_modules"](REPO_ROOT).values()),
        REPO_ROOT,
    ))
    assert findings == [], [f.render() for f in findings]


def test_dl014_registered():
    assert "DL014" in RULES
    assert RULES["DL014"].scope == "project"


# ---------------------------------------------------------------------------
# DL015 — exactly-once in-flight registry lifecycle (v3)
# ---------------------------------------------------------------------------

# acceptance fixture: PR 2's bug shape verbatim — submit_resume registers
# an on_done continuation in _pending_resumes, the crash sweep _fail_all
# drains _inflight but NOT _pending_resumes, so a member death leaves the
# resume's callback never run and the drain wedges
_DL015_PR2 = """
class EngineRunner:
    def __init__(self):
        self._inflight = {}
        self._pending_resumes = {}
    def submit(self, req):
        self._inflight[req.request_id] = req
    def submit_resume(self, exp, req, on_done):
        self._pending_resumes[req.request_id] = on_done
    def _drain_resume(self, rid):
        cb = self._pending_resumes.pop(rid, None)
        if cb is not None:
            cb(True, None)
    def _fail_all(self, err):
        for rid in list(self._inflight):
            req = self._inflight.pop(rid, None)
            if req is not None:
                req.sink.on_error(err)
"""


def test_dl015_pr2_fixture_resume_leak_past_fail_all_is_p0():
    out = pcheck("DL015", {f"{PKG}/serving/runner.py": _DL015_PR2})
    assert len(out) == 1, [f.render() for f in out]
    f = out[0]
    assert f.severity == "P0"
    assert "_pending_resumes" in f.message
    assert "crash path" in f.message
    # _inflight IS drained by _fail_all, so only the resume map flags
    assert "_inflight" not in f.message


def test_dl015_pr2_fixed_shape_is_clean():
    fixed = _DL015_PR2.replace(
        "            if req is not None:\n"
        "                req.sink.on_error(err)\n",
        "            if req is not None:\n"
        "                req.sink.on_error(err)\n"
        "        for rid in list(self._pending_resumes):\n"
        "            cb = self._pending_resumes.pop(rid, None)\n"
        "            if cb is not None:\n"
        "                cb(False, err)\n",
    )
    assert pcheck("DL015", {f"{PKG}/serving/runner.py": fixed}) == []


# acceptance fixture: PR 7's bug shape verbatim — _settle pops the entry
# FIRST and hands it to submit() after, so while the submit runs the
# request is in neither the registry nor the engine and a concurrent
# crash sweep cannot resolve it
_DL015_PR7 = """
class Dispatcher:
    def __init__(self):
        self._inflight = {}
    def enqueue(self, req):
        self._inflight[req.request_id] = req
    def _settle(self, rid):
        req = self._inflight.pop(rid, None)
        if req is None:
            return
        self.runner.submit(req)
    def _fail_all(self, err):
        for rid in list(self._inflight):
            self._inflight.pop(rid, None)
"""


def test_dl015_pr7_fixture_settle_pop_before_submit_is_p0():
    out = pcheck("DL015", {f"{PKG}/serving/dispatcher.py": _DL015_PR7})
    assert len(out) == 1, [f.render() for f in out]
    f = out[0]
    assert f.severity == "P0"
    assert "popped before the handoff" in f.message
    assert "_settle" in (f.context or "")


def test_dl015_pr7_handoff_first_shape_is_clean():
    fixed = _DL015_PR7.replace(
        "        req = self._inflight.pop(rid, None)\n"
        "        if req is None:\n"
        "            return\n"
        "        self.runner.submit(req)\n",
        "        req = self._inflight.pop(rid, None)\n"
        "        if req is None:\n"
        "            return\n",
    )
    assert pcheck("DL015", {f"{PKG}/serving/dispatcher.py": fixed}) == []


def test_dl015_state_map_with_crash_method_is_not_a_registry():
    # _members is membership STATE (expiry-pruned, no per-entry
    # continuation): the in-flight naming gate keeps it out even though
    # the class has a close() and add+del sites
    src = """
class Registry:
    def __init__(self):
        self._members = {}
    def observe(self, mid, rec):
        self._members[mid] = rec
    def prune(self, mid):
        del self._members[mid]
    def close(self):
        pass
"""
    assert pcheck("DL015", {f"{PKG}/serving/fleet.py": src}) == []


def test_dl015_marker_opts_in_and_no_resolve_anywhere_is_p0():
    src = """
class Router:
    def __init__(self):
        # distlint: registry
        self._routes = {}
    def learn(self, key, ep):
        self._routes[key] = ep
"""
    out = pcheck("DL015", {f"{PKG}/serving/fleet.py": src})
    assert len(out) == 1
    assert out[0].severity == "P0"
    assert "no pop/del/clear resolve site" in out[0].message


def test_dl015_read_before_pop_without_lock_is_p1():
    src = """
class Channel:
    def __init__(self):
        self._pending = {}
    def add(self, rid, cb):
        self._pending[rid] = cb
    def resolve(self, rid):
        cb = self._pending.get(rid)
        if cb is None:
            return
        self._pending.pop(rid, None)
        cb(True)
    def _fail_all(self):
        for rid in list(self._pending):
            self._pending.pop(rid, None)
"""
    out = pcheck("DL015", {f"{PKG}/serving/fleet_kv.py": src})
    assert len(out) == 1
    assert out[0].severity == "P1"
    assert "not pop-first gated" in out[0].message


def test_dl015_shared_lock_makes_check_then_act_atomic():
    src = """
import threading
class Channel:
    def __init__(self):
        self._lock = threading.Lock()
        self._pending = {}
    def add(self, rid, cb):
        with self._lock:
            self._pending[rid] = cb
    def resolve(self, rid):
        with self._lock:
            cb = self._pending.get(rid)
            if cb is None:
                return
            self._pending.pop(rid, None)
        cb(True)
    def _fail_all(self):
        with self._lock:
            for rid in list(self._pending):
                self._pending.pop(rid, None)
"""
    assert pcheck("DL015", {f"{PKG}/serving/fleet_kv.py": src}) == []


def test_dl015_locked_suffix_functions_are_exempt():
    src = """
class Rec:
    def __init__(self):
        self._streams = {}
    def add(self, rid, s):
        self._streams[rid] = s
    def _get_or_create_locked(self, rid):
        s = self._streams.get(rid)
        if s is None:
            self._streams.pop(rid, None)
        return s
    def _fail_all(self):
        for rid in list(self._streams):
            self._streams.pop(rid, None)
"""
    assert pcheck("DL015", {f"{PKG}/serving/flightrec.py": src}) == []


def test_dl015_registered():
    assert "DL015" in RULES
    assert RULES["DL015"].scope == "project"
    assert RULES["DL015"].severity == "P0"


# ---------------------------------------------------------------------------
# DL016 — exception-edge resource leak (v3)
# ---------------------------------------------------------------------------


def test_dl016_risky_call_between_dial_and_store_flags():
    src = """
import socket
class Channel:
    def _connect(self):
        sock = socket.create_connection(("h", 1), timeout=1.0)
        sock.setsockopt(1, 2, 3)
        self._sock = sock
"""
    out = pcheck("DL016", {f"{PKG}/serving/fleet_kv.py": src})
    assert len(out) == 1
    assert "dialed socket" in out[0].message
    assert "setsockopt" in out[0].message


def test_dl016_try_except_close_protects_the_edge():
    src = """
import socket
class Channel:
    def _connect(self):
        sock = socket.create_connection(("h", 1), timeout=1.0)
        try:
            sock.setsockopt(1, 2, 3)
        except OSError:
            sock.close()
            raise
        self._sock = sock
"""
    assert pcheck("DL016", {f"{PKG}/serving/fleet_kv.py": src}) == []


def test_dl016_socket_never_settled_flags():
    src = """
import socket
class Channel:
    def _probe(self):
        sock = socket.create_connection(("h", 1), timeout=1.0)
        sock.send(b"hi")
"""
    out = pcheck("DL016", {f"{PKG}/serving/fleet_kv.py": src})
    assert len(out) == 1
    assert "never released" in out[0].message


def test_dl016_breaker_token_risky_send_flags_and_handler_protects():
    leaky = """
class Channel:
    def _start(self):
        if not self.breaker.try_acquire():
            return False
        self.send_header()
        self.breaker.record_success()
        return True
"""
    out = pcheck("DL016", {f"{PKG}/serving/fleet_kv.py": leaky})
    assert len(out) == 1
    assert "breaker half-open token" in out[0].message
    guarded = """
class Channel:
    def _start(self):
        if not self.breaker.try_acquire():
            return False
        try:
            self.send_header()
        except OSError:
            self.breaker.record_failure()
            raise
        self.breaker.record_success()
        return True
"""
    assert pcheck("DL016", {f"{PKG}/serving/fleet_kv.py": guarded}) == []


def test_dl016_with_statement_consumes_the_acquire():
    src = """
import socket
class Channel:
    def _probe(self):
        with socket.create_connection(("h", 1), timeout=1.0) as sock:
            sock.send(b"hi")
"""
    assert pcheck("DL016", {f"{PKG}/serving/fleet_kv.py": src}) == []


def test_dl016_only_serving_modules_are_checked():
    src = """
import socket
def probe():
    sock = socket.create_connection(("h", 1), timeout=1.0)
    sock.send(b"hi")
"""
    assert pcheck("DL016", {f"{PKG}/engine/util.py": src}) == []


def test_dl016_registered():
    assert "DL016" in RULES
    assert RULES["DL016"].scope == "project"


# ---------------------------------------------------------------------------
# DL017 — wire-handler exhaustiveness (v3)
# ---------------------------------------------------------------------------

_DL017_WIRE = """
FRAME_KINDS = {1: "Ping", 2: "Pong", 3: "Data"}

def recv_frame(sock):
    kind = sock.read_u8()
    name = FRAME_KINDS.get(kind)
    return (name, {}) if name else None
"""

_DL017_READER = """
from x.wire import recv_frame

def read_loop(sock):
    while True:
        frame = recv_frame(sock)
        if frame is None:
            break
        name, obj = frame
        if name == "Ping":
            sock.pong()
        elif name == "Pong":
            pass
"""


def test_dl017_missing_arm_flags_with_marker_suggestion():
    out = pcheck("DL017", {
        f"{PKG}/serving/wire.py": _DL017_WIRE,
        f"{PKG}/serving/client.py": _DL017_READER,
    })
    assert len(out) == 1
    assert "'Data'" in out[0].message
    assert "wire-ignores[Data]" in out[0].message


def test_dl017_wire_ignores_marker_clears_the_arm():
    marked = _DL017_READER.replace(
        "def read_loop(sock):",
        "# distlint: wire-ignores[Data]\ndef read_loop(sock):")
    assert pcheck("DL017", {
        f"{PKG}/serving/wire.py": _DL017_WIRE,
        f"{PKG}/serving/client.py": marked,
    }) == []


def test_dl017_dead_arm_for_unknown_kind_flags():
    reader = _DL017_READER.replace(
        'elif name == "Pong":',
        'elif name == "Goodbye":\n'
        "            pass\n"
        '        elif name == "Data":\n'
        "            pass\n"
        '        elif name == "Pong":')
    out = pcheck("DL017", {
        f"{PKG}/serving/wire.py": _DL017_WIRE,
        f"{PKG}/serving/client.py": reader,
    })
    assert len(out) == 1
    assert "'Goodbye'" in out[0].message


def test_dl017_else_raise_default_is_intolerant():
    reader = _DL017_READER.replace(
        'elif name == "Pong":\n'
        "            pass",
        'elif name == "Pong":\n'
        "            pass\n"
        '        elif name == "Data":\n'
        "            pass\n"
        "        else:\n"
        "            raise ValueError(name)")
    out = pcheck("DL017", {
        f"{PKG}/serving/wire.py": _DL017_WIRE,
        f"{PKG}/serving/client.py": reader,
    })
    assert len(out) == 1
    assert "tolerate" in out[0].message


def test_dl017_non_dispatch_forwarder_is_skipped():
    # a helper that recv()s and forwards whole frames without
    # dispatching on the kind is not a reader loop
    fwd = """
from x.wire import recv_frame

def pump(sock, out):
    while True:
        frame = recv_frame(sock)
        if frame is None:
            break
        out.put(frame)
"""
    assert pcheck("DL017", {
        f"{PKG}/serving/wire.py": _DL017_WIRE,
        f"{PKG}/serving/relay.py": fwd,
    }) == []


def test_dl017_registered():
    assert "DL017" in RULES
    assert RULES["DL017"].scope == "project"


# ---------------------------------------------------------------------------
# DL018 — fault-point coverage drift (v3)
# ---------------------------------------------------------------------------

_DL018_FAULTS = '''
"""Fault injection.

``wire.send``      send dies on the wire
``engine.step``    crash mid-step
"""

def fire(point):
    pass
'''

_DL018_CHAOS = """
SCENARIOS = {"wire_death": "wire.send:nth=1"}
"""

_DL018_FAULTS_PATH = f"{PKG}/serving/faults.py"


def test_dl018_uncovered_point_flags_and_a_test_covers_it(tmp_path):
    sources = {
        _DL018_FAULTS_PATH: _DL018_FAULTS,
        "tools/chaos_fleet.py": _DL018_CHAOS,
    }
    (tmp_path / "tests").mkdir()
    out = pcheck("DL018", sources, root=tmp_path)
    assert len(out) == 1
    assert "'engine.step'" in out[0].message
    # a committed test arming the point clears the finding
    (tmp_path / "tests" / "test_cov.py").write_text(
        'faults.install(parse_spec("engine.step:nth=1", seed=1))\n')
    assert pcheck("DL018", sources, root=tmp_path) == []


def test_dl018_point_kwarg_in_tests_counts_as_exercised(tmp_path):
    sources = {
        _DL018_FAULTS_PATH: _DL018_FAULTS,
        "tools/chaos_fleet.py": _DL018_CHAOS,
    }
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_cov.py").write_text(
        'FaultRule(point="engine.step", nth=1)\n')
    assert pcheck("DL018", sources, root=tmp_path) == []


def test_dl018_file_restricted_run_is_silent(tmp_path):
    # without the faults module or the chaos module in view, coverage
    # cannot be judged — a --changed run must not false-positive
    assert pcheck("DL018", {
        _DL018_FAULTS_PATH: _DL018_FAULTS,
    }, root=tmp_path) == []


def test_dl018_real_repo_catalog_is_fully_exercised():
    findings = list(RULES["DL018"].check_project(
        list(run_lint.__globals__["collect_modules"](REPO_ROOT).values()),
        REPO_ROOT,
    ))
    assert findings == [], [f.render() for f in findings]


def test_dl018_registered():
    assert "DL018" in RULES
    assert RULES["DL018"].scope == "project"


# ---------------------------------------------------------------------------
# cache pruning (tools/lint/.cache; v3 satellite)
# ---------------------------------------------------------------------------


def test_prune_cache_evicts_corrupt_mismatched_and_old(tmp_path, monkeypatch):
    import os
    import pickle

    from tools.lint import callgraph

    monkeypatch.setattr(callgraph, "CACHE_DIR", tmp_path)

    def entry(name_key, stored_key, age):
        p = tmp_path / f"callgraph-{name_key}.pkl"
        with p.open("wb") as f:
            pickle.dump((stored_key, callgraph.ProjectSummary()), f)
        t = 1_700_000_000 - age
        os.utime(p, (t, t))
        return p

    # six valid entries, oldest first by age
    valid = [entry(f"key{i:02d}x", f"key{i:02d}x-full", age=i * 100)
             for i in range(6)]
    # a truncated/corrupt pickle and a key-mismatched one
    corrupt = tmp_path / "callgraph-deadbeef.pkl"
    corrupt.write_bytes(b"not a pickle")
    mismatched = entry("aaaa", "bbbb-full", age=1)

    evicted = callgraph.prune_cache(keep=4)
    # corrupt + mismatched always go; of the 6 valid, the 2 oldest go
    assert corrupt.name in evicted and mismatched.name in evicted
    assert not corrupt.exists() and not mismatched.exists()
    survivors = sorted(p.name for p in tmp_path.glob("callgraph-*.pkl"))
    assert survivors == sorted(p.name for p in valid[:4])


def test_prune_cache_keep_keys_survive_the_age_cut(tmp_path, monkeypatch):
    import os
    import pickle

    from tools.lint import callgraph

    monkeypatch.setattr(callgraph, "CACHE_DIR", tmp_path)
    for i in range(5):
        p = tmp_path / f"callgraph-key{i:02d}x.pkl"
        with p.open("wb") as f:
            pickle.dump((f"key{i:02d}x-full", callgraph.ProjectSummary()), f)
        t = 1_700_000_000 - i * 100
        os.utime(p, (t, t))
    # the OLDEST entry is the one just written by this run: it must
    # survive a keep=1 prune (an entry never evicts itself)
    callgraph.prune_cache(keep=1, keep_keys=("key04x",))
    names = {p.name for p in tmp_path.glob("callgraph-*.pkl")}
    assert "callgraph-key04x.pkl" in names
    assert "callgraph-key00x.pkl" in names  # newest valid survives keep=1


def test_build_summary_writes_and_prunes_through_the_real_path(
        tmp_path, monkeypatch):
    from tools.lint import callgraph

    monkeypatch.setattr(callgraph, "CACHE_DIR", tmp_path)
    stale = tmp_path / "callgraph-feedface.pkl"
    stale.write_bytes(b"junk")
    mods = [module_from_source(f"{PKG}/serving/m{i}.py", "x = 1\n")
            for i in range(12)]  # >= 10 modules => disk persistence
    callgraph._MEMO.clear()
    callgraph.build_summary(mods, use_disk_cache=True)
    names = [p.name for p in tmp_path.glob("callgraph-*.pkl")]
    assert len(names) == 1  # the fresh entry; the junk one was evicted
    assert not stale.exists()


# ---------------------------------------------------------------------------
# --timings (v3 satellite)
# ---------------------------------------------------------------------------


def test_run_lint_collects_per_rule_timings():
    timings = {}
    run_lint(REPO_ROOT, files=[f"{PKG}/serving/faults.py"],
             rules=["DL001", "DL004"], timings=timings)
    assert set(timings) == {"<collect>", "DL001", "DL004"}
    assert all(v >= 0.0 for v in timings.values())


def test_cli_timings_flag_prints_a_table(capsys):
    from tools.lint.run import main

    rc = main(["--rule", "DL010", "--timings",
               f"{PKG}/serving/faults.py"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "distlint timings" in out
    assert "DL010" in out and "total" in out
