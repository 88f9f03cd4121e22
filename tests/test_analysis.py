"""The project-invariant linter (repro.analysis): one known-bad fixture
per rule asserting the exact diagnostic, one suppressed fixture asserting
silence, revert-the-fix pins against the *real* tree (undoing the PR 7 GC
read-order fix or deleting a ``guarded by`` lock block must fail lint),
and the live-tree self-check — the regression gate that keeps the
annotations honest.

Everything here is pure stdlib and fast: the analyzer never imports the
code it checks.
"""

import json
from pathlib import Path

from repro.analysis import analyze
from repro.analysis.__main__ import main as cli_main

REPO = Path(__file__).resolve().parent.parent
SRC_REPRO = REPO / "src" / "repro"


def _lint_snippet(tmp_path, source, rules=None, relpath="repro/mod.py"):
    f = tmp_path / relpath
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(source)
    return analyze([str(f)], rules)


# ---------------------------------------------------------------------------
# lock-discipline


LOCKED_CLASS = '''
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []  #: guarded by self._lock

    def ok(self):
        with self._lock:
            self._items.append(1)

    def helper_locked(self):  # repro: holds[self._lock]
        return len(self._items)

    def bad(self):
        return list(self._items)
'''


def test_lock_discipline_catches_unlocked_access(tmp_path):
    diags = _lint_snippet(tmp_path, LOCKED_CLASS)
    assert [d.rule for d in diags] == ["lock-discipline"]
    d = diags[0]
    assert "Box._items is guarded by self._lock" in d.message
    # only the access in bad() fires — with-block and holds-method are fine
    assert d.line == LOCKED_CLASS.splitlines().index(
        "        return list(self._items)"
    ) + 1


def test_lock_discipline_suppression_silences(tmp_path):
    src = LOCKED_CLASS.replace(
        "        return list(self._items)",
        "        return list(self._items)  # repro: allow[lock-discipline]"
        " -- snapshot read, GIL-atomic",
    )
    assert _lint_snippet(tmp_path, src) == []


def test_lock_discipline_init_is_exempt_and_augassign_checked(tmp_path):
    src = '''
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0  #: guarded by self._lock

    def bump(self):
        self._n += 1
'''
    diags = _lint_snippet(tmp_path, src)
    assert [d.rule for d in diags] == ["lock-discipline"]
    assert "C._n" in diags[0].message


# ---------------------------------------------------------------------------
# clock-discipline


def test_clock_discipline_flags_wall_clock(tmp_path):
    src = (
        "import time as t\n"
        "from datetime import datetime\n"
        "a = t.time()\n"
        "b = datetime.now()\n"
        "c = t.localtime()\n"
        "d = t.localtime(123.0)\n"  # explicit epoch: allowed
        "e = t.perf_counter()\n"  # monotonic: allowed
    )
    diags = _lint_snippet(tmp_path, src)
    assert [(d.rule, d.line) for d in diags] == [
        ("clock-discipline", 3),
        ("clock-discipline", 4),
        ("clock-discipline", 5),
    ]


def test_clock_discipline_allows_clock_module(tmp_path):
    src = "import time\nnow = time.time()\n"
    assert _lint_snippet(tmp_path, src, relpath="repro/core/clock.py") == []
    assert len(_lint_snippet(tmp_path, src, relpath="repro/core/other.py")) == 1


def test_clock_discipline_suppression_silences(tmp_path):
    src = (
        "import time\n"
        "# repro: allow[clock-discipline] -- log file mtime stamp only\n"
        "t = time.time()\n"
    )
    assert _lint_snippet(tmp_path, src) == []


# ---------------------------------------------------------------------------
# decode-point


def test_decode_point_flags_raw_payload_io(tmp_path):
    src = (
        "import numpy as np\n"
        "from repro.core.tensor_io import load_tensor\n"
        "a = np.fromfile('x.bin', dtype='float32')\n"
        "b = load_tensor('x.npy', dtype='float32')\n"
        "fh = open('x.npy', 'rb')\n"
        "meta = open('meta.json')\n"  # text mode: allowed
    )
    diags = _lint_snippet(tmp_path, src)
    assert [(d.rule, d.line) for d in diags] == [
        ("decode-point", 3),
        ("decode-point", 4),
        ("decode-point", 5),
    ]
    assert "read layer" in diags[0].message


def test_decode_point_allows_read_layer_and_suppression(tmp_path):
    src = "import numpy as np\na = np.fromfile('x.bin', dtype='u1')\n"
    assert _lint_snippet(tmp_path, src, relpath="repro/core/dist_ckpt.py") == []
    sup = (
        "import numpy as np\n"
        "a = np.fromfile('x.bin', dtype='u1')  "
        "# repro: allow[decode-point] -- scratch file, not a shard\n"
    )
    assert _lint_snippet(tmp_path, sup) == []


# ---------------------------------------------------------------------------
# catalog


def _mini_tree(tmp_path, foo_source, catalog_extra="", obs_trace=None):
    """A minimal repro-shaped tree: registries + one call-site module
    (``catalog_extra`` is appended to ``obs/catalog.py``; ``obs_trace``,
    when given, is ``obs/trace.py``)."""
    (tmp_path / "repro/chaos").mkdir(parents=True)
    (tmp_path / "repro/obs").mkdir(parents=True)
    (tmp_path / "repro/ckpt").mkdir(parents=True)
    (tmp_path / "repro/chaos/points.py").write_text(
        'CATALOG: dict[str, str] = {\n'
        '    "saver.shard": "mid-save",\n'
        '    "gone.point": "no call site",\n'
        '}\n'
    )
    (tmp_path / "repro/obs/catalog.py").write_text(
        'SPANS: dict[str, str] = {"save.shard": "one shard"}\n'
        "TIMED: dict[str, str] = {}\n"
        "EVENTS: dict[str, str] = {}\n"
        "COUNTERS: dict[str, str] = {}\n" + catalog_extra
    )
    if obs_trace is not None:
        (tmp_path / "repro/obs/trace.py").write_text(obs_trace)
    (tmp_path / "repro/ckpt/saver.py").write_text(
        'from repro.chaos.points import fault_point\n'
        'import repro.obs as obs\n'
        'fault_point("saver.shard")\n'
        'with obs.span("save.shard"):\n'
        "    pass\n"
    )
    (tmp_path / "repro/foo.py").write_text(foo_source)
    return analyze([str(tmp_path / "repro")], ["catalog"])


def test_catalog_flags_unregistered_and_stale_names(tmp_path):
    diags = _mini_tree(
        tmp_path,
        'from repro.chaos.points import fault_point\n'
        'import repro.obs as obs\n'
        'fault_point(\n    "saver.typo",\n)\n'  # multi-line: regex would miss
        'obs.event("unregistered.event")\n',
    )
    msgs = [d.message for d in diags]
    assert any('"saver.typo" is not in chaos.points.CATALOG' in m for m in msgs)
    assert any(
        '"unregistered.event" is not in obs.catalog.EVENTS' in m for m in msgs
    )
    assert any('"gone.point" has no call site left' in m for m in msgs)
    assert len(diags) == 3


def test_catalog_checks_jit_spans_against_the_jax_mapping(tmp_path):
    """The jit.* spans have no obs.span call site: the values of
    obs/trace.py's JAX_SPANS / JAX_COUNTERS literals stand in for them."""
    diags = _mini_tree(
        tmp_path,
        "x = 1\n",
        catalog_extra='JIT_SPANS: dict[str, str] = {\n'
        '    "jit.trace": "traced",\n'
        '    "jit.stale": "no event maps here",\n'
        '}\n',
        obs_trace='JAX_SPANS = {"/jax/a": "jit.trace", "/jax/b": "jit.unlisted"}\n'
        'JAX_COUNTERS = {"/jax/c": "jit.cache_hits"}\n',
    )
    msgs = sorted(d.message for d in diags)
    assert any('"jit.unlisted" is not in obs.catalog.JIT_SPANS' in m for m in msgs)
    assert any('JIT_SPANS entry "jit.stale" has no call site left' in m for m in msgs)
    assert any('"jit.cache_hits" is not in obs.catalog.COUNTERS' in m for m in msgs)
    assert any('"gone.point" has no call site left' in m for m in msgs)
    assert len(diags) == 4


def test_catalog_requires_literal_names(tmp_path):
    diags = _mini_tree(
        tmp_path,
        'from repro.chaos.points import fault_point\n'
        'name = "saver.shard"\n'
        "fault_point(name)\n",
    )
    assert any(
        d.rule == "catalog" and "string literal" in d.message for d in diags
    )


def test_catalog_single_file_scan_skips_coverage(tmp_path):
    # linting one file must not report every catalog row as stale
    f = tmp_path / "solo.py"
    f.write_text("x = 1\n")
    assert analyze([str(f)], ["catalog"]) == []


# ---------------------------------------------------------------------------
# except-discipline


def test_except_discipline_flags_broad_handlers(tmp_path):
    src = (
        "try:\n    pass\nexcept Exception:\n    pass\n"
        "try:\n    pass\nexcept:\n    pass\n"
        "try:\n    pass\nexcept (ValueError, BaseException):\n    pass\n"
        "try:\n    pass\nexcept ValueError:\n    pass\n"  # narrow: allowed
    )
    diags = _lint_snippet(tmp_path, src)
    assert [d.rule for d in diags] == ["except-discipline"] * 3
    assert "except Exception" in diags[0].message
    assert "bare except" in diags[1].message


def test_except_discipline_allow_tag_silences(tmp_path):
    src = (
        "try:\n"
        "    pass\n"
        "except Exception:  # repro: allow[except-discipline] -- report, don't crash\n"
        "    pass\n"
    )
    assert _lint_snippet(tmp_path, src) == []


def test_reasonless_allow_is_itself_flagged(tmp_path):
    src = (
        "try:\n"
        "    pass\n"
        "except Exception:  # repro: allow[except-discipline]\n"
        "    pass\n"
    )
    diags = _lint_snippet(tmp_path, src)
    rules = sorted(d.rule for d in diags)
    assert rules == ["bad-suppression", "except-discipline"]


# ---------------------------------------------------------------------------
# regression pins: undo a shipped fix in the REAL tree, lint must fail


def _transformed_copy(tmp_path, rel, old, new):
    real = (SRC_REPRO / rel).read_text()
    assert real.count(old) == 1, f"pin anchor drifted in {rel}: {old!r}"
    out = tmp_path / "repro" / rel
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(real.replace(old, new))
    return out


def test_pin_gc_read_order_revert_fails_lint(tmp_path):
    out = _transformed_copy(
        tmp_path,
        "ckpt/manager.py",
        "        inflight = self._inflight_roots()\n        steps = self.steps()",
        "        steps = self.steps()\n        inflight = self._inflight_roots()",
    )
    diags = analyze([str(out)], ["regression-pin"])
    assert [d.rule for d in diags] == ["regression-pin"]
    assert "PR 7 read-order fix reverted" in diags[0].message
    # and the shipped file passes
    assert analyze([str(SRC_REPRO / "ckpt/manager.py")], ["regression-pin"]) == []


def test_pin_gc_newest_first_revert_fails_lint(tmp_path):
    out = _transformed_copy(
        tmp_path,
        "ckpt/manager.py",
        "for s in sorted(steps, reverse=True):",
        "for s in sorted(steps):",
    )
    diags = analyze([str(out)], ["regression-pin"])
    assert any("newest-first" in d.message for d in diags)


def test_deleting_guarded_lock_block_fails_lint(tmp_path):
    # PR 5 family: the delta-base pin set must only be touched under
    # _pin_lock; stripping the gc-side lock block must trip the checker.
    out = _transformed_copy(
        tmp_path,
        "ckpt/manager.py",
        """        with self._pin_lock:
            # pins die with their save: drop entries whose save finished
            self._pinned_chains = {
                r: c for r, c in self._pinned_chains.items() if r in inflight
            }""",
        """        # pins die with their save: drop entries whose save finished
        self._pinned_chains = {
            r: c for r, c in self._pinned_chains.items() if r in inflight
        }""",
    )
    diags = analyze([str(out)], ["lock-discipline"])
    assert diags and all(d.rule == "lock-discipline" for d in diags)
    assert any("_pinned_chains" in d.message for d in diags)


# ---------------------------------------------------------------------------
# live tree + CLI


def test_live_tree_is_clean():
    """The shipped tree lints clean — this is the audited-clean pin for
    the annotated classes (registry, drain, engine, hot tier, obs, chaos;
    see DESIGN.md §11) and the gate that keeps future edits honest."""
    assert analyze([str(SRC_REPRO)]) == []


def test_cli_json_format(tmp_path, capsys):
    f = tmp_path / "bad.py"
    f.write_text("import time\nt = time.time()\n")
    rc = cli_main([str(f), "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out[0]["rule"] == "clock-discipline"
    assert out[0]["line"] == 2

    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    assert cli_main([str(ok)]) == 0


def test_cli_rejects_unknown_rule_and_path(tmp_path, capsys):
    assert cli_main(["--rule", "nope", str(tmp_path)]) == 2
    assert cli_main([str(tmp_path / "missing")]) == 2
