"""The analyzer analyzed: seeded-violation fixtures per rule (per-file
VL001-VL005/VL105/VL106/VL301 and interprocedural VL101-VL104), call-graph
resolution
over the committed mini-package in ``analysis_fixtures/``, baseline
add/expire, suppression comments, SARIF emission, the incremental
cache, and the tier-1 gate — `volsync lint` runs clean over the
shipped package and ``scripts/`` with NO baseline."""

import json
from pathlib import Path

import volsync_tpu
from volsync_tpu.analysis import (
    apply_baseline,
    load_baseline,
    run_lint,
    run_project,
    write_baseline,
)
from volsync_tpu.analysis.cli import main as lint_main
from volsync_tpu.cli.main import run as cli_run

FIXTURES = Path(__file__).resolve().parent / "analysis_fixtures"


def _lint_file(tmp_path, source, name="mod.py", subdir=None):
    d = tmp_path if subdir is None else tmp_path / subdir
    d.mkdir(parents=True, exist_ok=True)
    f = d / name
    f.write_text(source)
    findings, errors = run_lint([str(f)])
    assert errors == []
    return findings


def _codes(findings):
    return sorted(f.code for f in findings)


# -- rule fixtures ----------------------------------------------------------

def test_vl001_env_read_flagged(tmp_path):
    src = (
        "import os\n"
        "import os as _os\n"
        "from os import environ, getenv as ge\n"
        "a = os.environ.get('VOLSYNC_FOO')\n"
        "b = _os.environ['VOLSYNC_BAR']\n"
        "c = environ.get('VOLSYNC_BAZ')\n"
        "d = ge('VOLSYNC_QUX')\n"
        "e = 'VOLSYNC_IN' in os.environ\n"
        "ok1 = os.environ.get('HOME')\n"          # not VOLSYNC_*
        "ok2 = os.environ.get(a)\n"               # non-literal key
        "os.environ['VOLSYNC_SET'] = '1'\n"       # write, not read
    )
    findings = _lint_file(tmp_path, src)
    assert _codes(findings) == ["VL001"] * 5
    assert {f.line for f in findings} == {4, 5, 6, 7, 8}


def test_vl001_envflags_exempt(tmp_path):
    src = "import os\nx = os.environ.get('VOLSYNC_FOO')\n"
    findings = _lint_file(tmp_path, src, name="envflags.py")
    assert findings == []


def test_vl002_gated_imports(tmp_path):
    src = ("import zstandard\n"
           "from cryptography.hazmat.primitives import hashes\n"
           "import json\n")
    findings = _lint_file(tmp_path, src)
    assert _codes(findings) == ["VL002", "VL002"]
    # ...but fine inside the shims
    assert _lint_file(tmp_path, "import zstandard\n",
                      name="compress.py", subdir="repo") == []
    assert _lint_file(tmp_path, "import cryptography\n",
                      name="crypto.py", subdir="repo") == []


def test_vl003_silent_swallow(tmp_path):
    src = (
        "try:\n    x = 1\nexcept Exception:\n    pass\n"
        "try:\n    x = 2\nexcept:\n    pass\n"
        "for i in range(3):\n"
        "    try:\n        x = 3\n    except BaseException:\n"
        "        continue\n"
        # narrow type: allowed
        "try:\n    x = 4\nexcept ValueError:\n    pass\n"
        # broad but logged: allowed
        "try:\n    x = 5\nexcept Exception as e:\n    print(e)\n"
        # broad but re-raised: allowed
        "try:\n    x = 6\nexcept Exception:\n    raise\n"
    )
    findings = _lint_file(tmp_path, src)
    assert _codes(findings) == ["VL003"] * 3


def test_vl003_suppression_comment(tmp_path):
    src = ("try:\n    x = 1\n"
           "except Exception:  # lint: ignore[VL003] — reason here\n"
           "    pass\n"
           "try:\n    x = 2\n"
           "except Exception:  # lint: ignore\n"
           "    pass\n"
           "try:\n    x = 3\n"
           "except Exception:  # lint: ignore[VL001]\n"  # wrong code
           "    pass\n")
    findings = _lint_file(tmp_path, src)
    assert _codes(findings) == ["VL003"]
    assert findings[0].line == 11


def test_vl004_tracer_safety(tmp_path):
    src = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, static_argnames=('n',))\n"
        "def f(x, n):\n"
        "    if x > 0:\n"            # VL004: branch on traced arg
        "        return float(x)\n"  # VL004: float() on traced
        "    if n > 2:\n"            # static arg: allowed
        "        return x.item()\n"  # VL004: .item()
        "    if x.shape[0] == 1:\n"  # shape access: static, allowed
        "        return x\n"
        "    if x is None:\n"        # identity check: allowed
        "        return x\n"
        "    return x\n"
        "def host(x):\n"
        "    return float(x)\n"      # not jit'd: allowed
    )
    findings = _lint_file(tmp_path, src, subdir="ops")
    assert _codes(findings) == ["VL004"] * 3
    assert {f.line for f in findings} == {5, 6, 8}
    # same file OUTSIDE an ops/ dir: rule out of scope
    assert _lint_file(tmp_path, src, subdir="host") == []


def test_vl005_direct_lock(tmp_path):
    src = ("import threading\n"
           "from threading import Lock\n"
           "a = threading.Lock()\n"
           "b = threading.RLock()\n"
           "c = Lock()\n"
           "e = threading.Event()\n")  # not a lock: allowed
    findings = _lint_file(tmp_path, src, subdir="repo")
    assert _codes(findings) == ["VL005"] * 3
    # out of data-plane scope: allowed
    assert _lint_file(tmp_path, src, subdir="cluster") == []


def test_vl105_adhoc_retry(tmp_path):
    src = (
        "import time\n"
        "import time as t\n"
        "from time import sleep as zzz\n"
        "def handler():\n"
        "    try:\n"
        "        x = 1\n"
        "    except OSError:\n"
        "        time.sleep(1)\n"       # VL105: sleep in except
        "def retry_loop():\n"
        "    for i in range(3):\n"
        "        try:\n"
        "            x = 1\n"
        "        except OSError:\n"
        "            pass\n"
        "        t.sleep(0.1)\n"        # VL105: sleep in retry loop
        "def while_retry():\n"
        "    while True:\n"
        "        try:\n"
        "            break\n"
        "        except OSError:\n"
        "            pass\n"
        "        zzz(0.1)\n"            # VL105: aliased from-import
        "def pacing():\n"
        "    for i in range(3):\n"      # loop without a try: pacing,
        "        time.sleep(0.1)\n"     # not a retry loop — allowed
        "def nested_reset():\n"
        "    try:\n"
        "        x = 1\n"
        "    except OSError:\n"
        "        def cb():\n"           # new function scope resets
        "            time.sleep(1)\n"   # the except context — allowed
        "        cb()\n"
    )
    findings = _lint_file(tmp_path, src)
    assert _codes(findings) == ["VL105"] * 3
    assert {f.line for f in findings} == {8, 15, 22}
    # resilience.py implements the policy — exempt
    assert _lint_file(tmp_path, src, name="resilience.py") == []


def test_vl105_suppression(tmp_path):
    src = ("import time\n"
           "while True:\n"
           "    try:\n"
           "        break\n"
           "    except OSError:\n"
           "        pass\n"
           "    time.sleep(1)  # lint: ignore[VL105] — paced poll\n")
    assert _lint_file(tmp_path, src) == []


def test_vl106_hot_path_copies(tmp_path):
    src = (
        "def seal(view, parts, n):\n"
        "    a = view.tobytes()\n"                  # VL106: materializes
        "    b = bytes(view)\n"                     # VL106: buffer copy
        "    c = b''.join(parts)\n"                 # VL106: contiguous join
        "    ok1 = bytes(16)\n"                     # allocation, not a copy
        "    ok2 = bytes()\n"                       # empty, no argument
        "    ok3 = ','.join(str(p) for p in parts)\n"  # str join
        "    ok4 = n.to_bytes(8, 'big')\n"          # int serialization
        "    return a, b, c, ok1, ok2, ok3, ok4\n"
    )
    findings = _lint_file(tmp_path, src, subdir="repo")
    assert _codes(findings) == ["VL106"] * 3
    assert {f.line for f in findings} == {2, 3, 4}
    # engine/ and ops/ are data-plane scope too; the service plane and
    # cluster control plane are not
    assert _codes(_lint_file(tmp_path, src, subdir="engine")) == ["VL106"] * 3
    assert _lint_file(tmp_path, src, subdir="service") == []
    assert _lint_file(tmp_path, src, subdir="cluster") == []


def test_vl106_suppression(tmp_path):
    src = ("def download(digests):\n"
           "    return digests.tobytes()  # lint: ignore[VL106] 32 B digests\n")
    assert _lint_file(tmp_path, src, subdir="ops") == []


def test_vl301_dynamic_span_names_flagged(tmp_path):
    src = (
        "from volsync_tpu.obs import begin_span, span\n"
        "from volsync_tpu import obs\n"
        "stage = 'read'\n"
        "with span(f'engine.{stage}'):\n"      # f-string
        "    pass\n"
        "with span('engine.' + stage):\n"      # concatenation
        "    pass\n"
        "with span(stage):\n"                  # variable
        "    pass\n"
        "with span('Bad.Name'):\n"             # not lowercase
        "    pass\n"
        "with obs.span('flat'):\n"             # no dot: not component.stage
        "    pass\n"
        "h = begin_span(name=stage)\n"         # name= kwarg, variable
    )
    findings = _lint_file(tmp_path, src)
    assert _codes(findings) == ["VL301"] * 6
    assert {f.line for f in findings} == {4, 6, 8, 10, 12, 14}


def test_vl301_clean_twin(tmp_path):
    src = (
        "import re\n"
        "from volsync_tpu.obs import begin_span, span\n"
        "from volsync_tpu import obs\n"
        "with span('engine.read'):\n"
        "    pass\n"
        "with obs.span('svc.queue_wait', lanes=4):\n"  # attrs carry detail
        "    pass\n"
        "h = begin_span('repo.pack_upload', ctx=None)\n"
        "h.finish('ok')\n"
        "m = re.match('(a)', 'a')\n"
        "s = m.span(1)\n"       # re.Match.span — not a tracing receiver
    )
    assert _lint_file(tmp_path, src) == []
    # the tracing module defines span()/begin_span() and forwards
    # caller-supplied names internally — exempt
    dynamic = ("def span(name, **attrs):\n"
               "    return name\n"
               "x = 'dyn'\n"
               "span(x)\n")
    assert _lint_file(tmp_path, dynamic, name="tracing.py",
                      subdir="obs") == []
    assert _codes(_lint_file(tmp_path, dynamic)) == ["VL301"]


def test_syntax_error_is_reported(tmp_path):
    f = tmp_path / "bad.py"
    f.write_text("def broken(:\n")
    findings, errors = run_lint([str(f)])
    assert findings == []
    assert len(errors) == 1 and "bad.py" in errors[0]


# -- interprocedural rules (call graph + dataflow) --------------------------

def _mark_line(path: Path, marker: str) -> int:
    """1-based line of the fixture statement tagged ``# MARK: <marker>``."""
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        if f"MARK: {marker}" in line:
            return i
    raise AssertionError(f"marker {marker!r} not in {path}")


def test_vl101_interprocedural_fixture_package():
    """The committed mini-package exercises the resolver end to end:
    from-import-as aliasing, self-method dispatch, base-class lock
    lookup — and a blocking call TWO call-hops below a ``with lock:``
    region is reported with its hop chain."""
    res = run_project([str(FIXTURES / "miniproj")])
    assert res.errors == []
    store = FIXTURES / "miniproj" / "repo" / "store.py"
    vl101 = [f for f in res.findings if f.code == "VL101"]
    assert all(f.path.endswith("repo/store.py") for f in vl101)
    by_line = {f.line: f for f in vl101}
    assert set(by_line) == {_mark_line(store, "direct-sleep"),
                            _mark_line(store, "two-hop"),
                            _mark_line(store, "self-method")}

    direct = by_line[_mark_line(store, "direct-sleep")]
    assert "time.sleep()" in direct.message
    assert "lock 'miniproj.repo.module'" in direct.message

    # the acceptance example: sink two hops below the region header,
    # found through an aliased from-import (`drain as pump`)
    two_hop = by_line[_mark_line(store, "two-hop")]
    assert "via drain() -> _slow()" in two_hop.message
    assert "lock 'miniproj.repo.store'" in two_hop.message
    assert two_hop.severity == "error"

    # self-method call resolved through the subclass, lock attribute
    # resolved through the base class
    self_m = by_line[_mark_line(store, "self-method")]
    assert "via _write() -> drain() -> _slow()" in self_m.message
    # flush_ok (call outside the region) and the suppressed `reviewed`
    # region produced nothing — the three above are ALL the findings


def test_vl104_interprocedural_taint_fixture():
    """Traced values flowing through helper calls (module alias and
    from-import alias) into host branches, and branches on
    tracer-derived locals."""
    res = run_project([str(FIXTURES / "miniproj")])
    kern = FIXTURES / "miniproj" / "ops" / "kern.py"
    vl104 = [f for f in res.findings if f.code == "VL104"]
    assert all(f.path.endswith("ops/kern.py") for f in vl104)
    by_line = {f.line: f for f in vl104}
    assert set(by_line) == {_mark_line(kern, "taint-via-route"),
                            _mark_line(kern, "derived-branch"),
                            _mark_line(kern, "taint-direct")}
    via = by_line[_mark_line(kern, "taint-via-route")]
    assert "via route() -> decide()" in via.message
    assert via.severity == "error"
    derived = by_line[_mark_line(kern, "derived-branch")]
    assert "tracer-derived" in derived.message and "'z'" in derived.message
    direct = by_line[_mark_line(kern, "taint-direct")]
    assert "decide(" in direct.message
    # nothing else fires on the fixture package beyond the seeded
    # VL2xx shape/dtype bugs (asserted in test_analysis_shapes.py),
    # the locks/ concurrency fixtures (test_analysis_locks.py), the
    # buf/ buffer-provenance fixtures (test_analysis_buf.py) and the
    # fx/ fault-path fixtures (test_analysis_fx.py)
    assert {f.code for f in res.findings} == {
        "VL101", "VL104", "VL201", "VL202", "VL203", "VL204", "VL205",
        "VL401", "VL402", "VL403", "VL404",
        "VL501", "VL502", "VL503", "VL504", "VL505",
        "VL601", "VL602", "VL603", "VL604", "VL605"}


def test_vl101_regions_and_comment_above_suppression(tmp_path):
    src = (
        "import time\n"
        "def make_lock(name):\n"
        "    return name\n"
        "_L = make_lock('t.lock')\n"
        "def hot():\n"
        "    with _L:\n"
        "        time.sleep(1)\n"
        "def reviewed():\n"
        "    # lint: ignore[VL101] -- held for atomicity only\n"
        "    with _L:\n"
        "        time.sleep(1)\n"
        "def bare():\n"
        "    _L.acquire()\n"
        "    try:\n"
        "        time.sleep(1)\n"
        "    finally:\n"
        "        _L.release()\n"
        "def after_release():\n"
        "    _L.acquire()\n"
        "    try:\n"
        "        pass\n"
        "    finally:\n"
        "        _L.release()\n"
        "    time.sleep(1)\n"
    )
    findings = _lint_file(tmp_path, src, subdir="engine")
    assert _codes(findings) == ["VL101", "VL101"]
    # the with-region sink and the bare acquire()..release() region
    # sink; the comment-above suppression and post-release sleep don't
    assert {f.line for f in findings} == {7, 15}


def test_vl102_thread_lifecycle(tmp_path):
    src = (
        "import threading\n"
        "from concurrent.futures import ThreadPoolExecutor\n"
        "def unnamed_daemon():\n"
        "    threading.Thread(target=print, daemon=True).start()\n"
        "def named_joined():\n"
        "    t = threading.Thread(target=print, name='w')\n"
        "    t.start()\n"
        "    t.join()\n"
        "def named_leaked():\n"
        "    t = threading.Thread(target=print, name='w2')\n"
        "    t.start()\n"
        "def pool_leaked():\n"
        "    ex = ThreadPoolExecutor(max_workers=2)\n"
        "    return ex.submit(print)\n"
        "def pool_with():\n"
        "    with ThreadPoolExecutor(max_workers=2) as ex:\n"
        "        ex.submit(print)\n"
        "def pool_transferred(server):\n"
        "    return server(ThreadPoolExecutor(max_workers=2))\n"
    )
    findings = _lint_file(tmp_path, src)
    assert _codes(findings) == ["VL102"] * 3
    assert {f.line for f in findings} == {4, 10, 13}
    msgs = " / ".join(f.message for f in findings)
    assert "without name=" in msgs
    assert "no reachable .join()" in msgs
    assert "no reachable .shutdown()" in msgs


def test_vl103_exception_path_leak(tmp_path):
    src = (
        "def leak(lock):\n"
        "    lock.acquire()\n"
        "    do()\n"
        "    lock.release()\n"
        "def ok_finally(lock):\n"
        "    lock.acquire()\n"
        "    try:\n"
        "        do()\n"
        "    finally:\n"
        "        lock.release()\n"
        "def ok_reraise(slots):\n"
        "    slots.acquire()\n"
        "    try:\n"
        "        do()\n"
        "    except Exception:\n"
        "        slots.release()\n"
        "        raise\n"
        "def leak_open(p):\n"
        "    f = open(p)\n"
        "    return f.read()\n"
        "def ok_open(p):\n"
        "    f = open(p)\n"
        "    try:\n"
        "        return f.read()\n"
        "    finally:\n"
        "        f.close()\n"
        "def ok_with(p):\n"
        "    with open(p) as f:\n"
        "        return f.read()\n"
    )
    findings = _lint_file(tmp_path, src, subdir="repo")
    assert _codes(findings) == ["VL103", "VL103"]
    assert {f.line for f in findings} == {2, 19}
    # out of the data-plane scope the rule stays silent
    assert _lint_file(tmp_path, src, subdir="cluster") == []


# -- incremental cache ------------------------------------------------------

def test_cache_warm_run_and_transitive_invalidation(tmp_path):
    a, b, c = (tmp_path / n for n in ("a.py", "b.py", "c.py"))
    c.write_text("import os\n"
                 "import time\n"
                 "V = os.environ.get('VOLSYNC_CACHED')\n"
                 "def slow():\n"
                 "    time.sleep(1)\n")
    b.write_text("import c\n"
                 "def mid():\n"
                 "    c.slow()\n")
    a.write_text("import b\n"
                 "def top():\n"
                 "    b.mid()\n")
    cache = tmp_path / ".lint-cache"

    cold = run_project([str(tmp_path)], cache_path=cache)
    assert cold.errors == []
    assert sorted(cold.analyzed) == sorted(
        p.as_posix() for p in (a, b, c))
    assert [f.code for f in cold.findings] == ["VL001"]

    # warm: identical tree -> ZERO files re-analyzed, findings served
    # verbatim from the cache
    warm = run_project([str(tmp_path)], cache_path=cache)
    assert warm.analyzed == []
    assert warm.total == 3
    assert [(f.path, f.line, f.code, f.message, f.severity)
            for f in warm.findings] == [
        (f.path, f.line, f.code, f.message, f.severity)
        for f in cold.findings]

    # editing the leaf callee re-analyzes it AND its transitive
    # reverse importers (b imports c, a imports b)
    c.write_text(c.read_text().replace("time.sleep(1)", "time.sleep(2)"))
    edited = run_project([str(tmp_path)], cache_path=cache)
    assert sorted(edited.analyzed) == sorted(
        p.as_posix() for p in (a, b, c))

    # an unrelated new file re-analyzes only itself
    d = tmp_path / "d.py"
    d.write_text("X = 1\n")
    extended = run_project([str(tmp_path)], cache_path=cache)
    assert extended.analyzed == [d.as_posix()]
    assert [f.code for f in extended.findings] == ["VL001"]


def test_cache_rejected_on_rule_set_change(tmp_path):
    mod = tmp_path / "m.py"
    mod.write_text("X = 1\n")
    cache = tmp_path / ".lint-cache"
    run_project([str(tmp_path)], cache_path=cache)

    class FakeRule:
        code = "VL999"
        name = "fake"
        description = "fake"

        def check(self, ctx):
            return iter(())

    from volsync_tpu.analysis.rules import default_rules
    res = run_project([str(tmp_path)], rules=default_rules() + [FakeRule()],
                      cache_path=cache)
    # different rule signature -> cache miss -> full re-analysis
    assert res.analyzed == [mod.as_posix()]


def test_cli_cache_stat_line(tmp_path):
    mod = tmp_path / "m.py"
    mod.write_text("X = 1\n")
    cache = tmp_path / ".lint-cache"
    lines = []
    rc = lint_main([str(mod), "--no-baseline", "--cache", str(cache)],
                   out=lines.append)
    assert rc == 0
    lines.clear()
    rc = lint_main([str(mod), "--no-baseline", "--cache", str(cache)],
                   out=lines.append)
    assert rc == 0
    assert any(ln.startswith("cache: analyzed 0 of 1") for ln in lines)


# -- SARIF ------------------------------------------------------------------

def test_sarif_output_shape(tmp_path):
    mod = tmp_path / "m.py"
    mod.write_text("import os\nx = os.environ.get('VOLSYNC_X')\n")
    out_file = tmp_path / "lint.sarif"
    lines = []
    rc = lint_main([str(mod), "--no-baseline", "--format", "sarif",
                    "--out", str(out_file)], out=lines.append)
    assert rc == 1
    doc = json.loads(out_file.read_text())
    assert doc["version"] == "2.1.0"
    assert "sarif-schema-2.1.0.json" in doc["$schema"]
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "volsync-lint"
    rule_ids = [r["id"] for r in driver["rules"]]
    for code in ("VL001", "VL101", "VL102", "VL103", "VL104"):
        assert code in rule_ids
    for r in driver["rules"]:
        assert r["defaultConfiguration"]["level"] in (
            "error", "warning", "note")
    assert run["invocations"][0]["executionSuccessful"] is True
    (res,) = run["results"]
    assert res["ruleId"] == "VL001"
    assert res["level"] == "warning"
    assert res["message"]["text"]
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("m.py")
    assert loc["region"]["startLine"] == 2
    assert rule_ids[res["ruleIndex"]] == "VL001"


def test_sarif_parse_error_notification(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    lines = []
    rc = lint_main([str(bad), "--no-baseline", "--format", "sarif"],
                   out=lines.append)
    assert rc == 1
    doc = json.loads("\n".join(lines))
    inv = doc["runs"][0]["invocations"][0]
    assert inv["executionSuccessful"] is False
    notes = inv["toolExecutionNotifications"]
    assert len(notes) == 1 and "bad.py" in notes[0]["message"]["text"]


# -- baseline add / expire --------------------------------------------------

def test_baseline_roundtrip_and_expiry(tmp_path):
    mod = tmp_path / "legacy.py"
    mod.write_text("import os\n"
                   "a = os.environ.get('VOLSYNC_OLD')\n"
                   "b = os.environ.get('VOLSYNC_OLDER')\n")
    baseline_path = tmp_path / "baseline.json"

    findings, _ = run_lint([str(mod)])
    assert len(findings) == 2
    write_baseline(findings, baseline_path)

    # grandfathered: nothing new
    baseline = load_baseline(baseline_path)
    new, suppressed, stale = apply_baseline(findings, baseline)
    assert new == [] and suppressed == 2 and stale == []

    # a NEW violation is not covered by the old allowance
    mod.write_text(mod.read_text()
                   + "c = os.environ.get('VOLSYNC_NEW')\n")
    findings2, _ = run_lint([str(mod)])
    new, suppressed, stale = apply_baseline(findings2,
                                            load_baseline(baseline_path))
    assert len(new) == 1 and "VOLSYNC_NEW" in new[0].message
    assert suppressed == 2

    # fixing a grandfathered finding EXPIRES its baseline entry
    mod.write_text("import os\n"
                   "a = os.environ.get('VOLSYNC_OLD')\n")
    findings3, _ = run_lint([str(mod)])
    new, suppressed, stale = apply_baseline(findings3,
                                            load_baseline(baseline_path))
    assert new == [] and suppressed == 1
    assert len(stale) == 1 and "VOLSYNC_OLDER" in stale[0]


def test_baseline_missing_file_is_empty(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == {}


def test_cli_exit_codes_and_write_baseline(tmp_path):
    mod = tmp_path / "m.py"
    mod.write_text("import os\nx = os.environ.get('VOLSYNC_X')\n")
    baseline = tmp_path / "b.json"
    lines = []

    rc = lint_main([str(mod), "--baseline", str(baseline)],
                   out=lines.append)
    assert rc == 1
    assert any("VL001" in ln for ln in lines)

    rc = lint_main([str(mod), "--baseline", str(baseline),
                    "--write-baseline"], out=lines.append)
    assert rc == 0 and baseline.exists()

    rc = lint_main([str(mod), "--baseline", str(baseline)],
                   out=lines.append)
    assert rc == 0

    # --no-baseline reports everything again
    rc = lint_main([str(mod), "--baseline", str(baseline),
                    "--no-baseline"], out=lines.append)
    assert rc == 1


def test_volsync_cli_lint_verb(tmp_path):
    """`volsync lint` dispatches to the analyzer without needing any
    cluster context."""
    mod = tmp_path / "m.py"
    mod.write_text("try:\n    pass\nexcept Exception:\n    pass\n")
    lines = []
    rc = cli_run(["lint", str(mod), "--no-baseline"], {},
                 out=lines.append)
    assert rc == 1
    assert any("VL003" in ln for ln in lines)


# -- the tier-1 gate --------------------------------------------------------

def test_package_is_lint_clean():
    """The whole shipped tree — the package and ``scripts/`` —
    passes every rule (per-file AND interprocedural) with NO
    baseline: the repo's stated invariants (env reads via
    envflags, gated imports, no silent swallows, tracer-safe kernels,
    lockcheck-routed locks, no blocking I/O under locks, named/joined
    threads, exception-safe acquires) hold right now, and this test
    keeps them held."""
    pkg = Path(volsync_tpu.__file__).resolve().parent
    paths = [str(pkg)]
    repo_root = pkg.parent
    scripts = repo_root / "scripts"
    if scripts.exists():  # absent when only the package is installed
        paths.append(str(scripts))
    findings, errors = run_lint(paths)
    assert errors == []
    assert findings == [], "\n" + "\n".join(f.render() for f in findings)
