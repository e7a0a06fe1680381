"""persia-lint (persia_tpu.analysis) + sanitizer-variant build tests.

Two halves:

- seeded-violation fixtures under tests/fixtures/analysis/ — one bad
  snippet per rule — assert every rule FIRES (a lint whose rules can rot
  silently is worse than no lint);
- the clean-tree gate — the real repo must produce ZERO findings with
  full coverage (5 native libs, all registered binding files), which is
  exactly what scripts/round_preflight.sh step 0 enforces.

Plus unit coverage for the sanitizer-variant native builds: distinct
artifact names, flag/variant folding into the srchash (a flag change must
rebuild), and a real UBSan compile through build_so.
"""

import logging
import os
import subprocess
import sys

import pytest

from persia_tpu.analysis import (
    abi,
    concurrency,
    cparse,
    interproc,
    jax_lint,
    protocol,
    resilience_lint,
    run_all,
)
from persia_tpu.analysis.common import (
    CTYPES_FILES,
    NATIVE_LIBS,
    REPO_ROOT,
    apply_suppressions,
    read_text,
)
from persia_tpu.embedding import _native_build

FIXDIR = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")
FX_LIBS = {"libfx.so": ["fake_native.cpp"]}

logger = logging.getLogger("test_analysis")


def _fixture(name: str) -> str:
    return os.path.join(FIXDIR, name)


def _abi_rules(binding_file: str):
    findings, _cov = abi.check(
        root=FIXDIR, binding_files=[_fixture(binding_file)], libs=FX_LIBS
    )
    return findings, {f.rule for f in findings}


# --------------------------------------------------------------- C parser


def test_cparse_fake_surface():
    funcs, warns = cparse.parse_extern_c(
        read_text(_fixture("fake_native.cpp")), "fake_native.cpp"
    )
    assert warns == []
    by_name = {f.name: f for f in funcs}
    assert set(by_name) == {"fx_create", "fx_destroy", "fx_len", "fx_touch", "fx_orphan"}
    assert by_name["fx_create"].ret == ("ptr", ("void",))
    assert by_name["fx_touch"].ret == ("void",)
    assert by_name["fx_touch"].params == [
        ("ptr", ("void",)), ("ptr", ("int", 64, False)), ("int", 64, True),
    ]


def test_cparse_real_surfaces_parse_fully():
    """All five production libs parse with no warnings and plausible
    export counts — the coverage the clean-tree gate depends on."""
    for lib, sources in NATIVE_LIBS.items():
        for src in sources:
            funcs, warns = cparse.parse_extern_c(
                read_text(os.path.join(REPO_ROOT, src)), src
            )
            assert warns == [], f"{src}: {warns}"
            assert funcs, f"{src} parsed zero extern C declarations"


# ------------------------------------------------------------ ABI fixtures


@pytest.mark.parametrize(
    "fixture, rule",
    [
        ("abi_bad_arity.py", "ABI001"),
        ("abi_bad_width.py", "ABI002"),
        ("abi_missing_restype.py", "ABI003"),
        ("abi_bad_restype.py", "ABI004"),
        ("abi_unknown_symbol.py", "ABI005"),
        ("abi_missing_argtypes.py", "ABI007"),
        ("abi_untyped_call.py", "ABI008"),
    ],
)
def test_abi_rule_fires(fixture, rule):
    findings, rules = _abi_rules(fixture)
    assert rule in rules, f"{fixture}: expected {rule}, got {findings}"


def test_abi_unbound_export_fires():
    # any fixture that leaves fx_orphan unbound triggers ABI006 on the cpp
    findings, rules = _abi_rules("abi_missing_restype.py")
    assert "ABI006" in rules
    orphaned = [f for f in findings if f.rule == "ABI006"]
    assert any("fx_orphan" in f.message for f in orphaned)


def test_abi_clean_bindings_zero_findings():
    findings, cov = abi.check(
        root=FIXDIR, binding_files=[_fixture("abi_clean.py")], libs=FX_LIBS
    )
    assert findings == [], findings
    assert cov["libs"] == {"libfx.so": 5}


def test_abi009_registry_covers_every_cdll_loader():
    """Registry completeness (ABI009): every persia_tpu/ file that calls
    ctypes.CDLL is listed in CTYPES_FILES — including the tiering sketch
    bindings — so the drift checker cannot silently skip a loader."""
    from persia_tpu.analysis.common import ctypes_loader_files

    loaders = ctypes_loader_files(REPO_ROOT)
    assert "persia_tpu/embedding/tiering/native.py" in loaders
    unregistered = sorted(set(loaders) - set(CTYPES_FILES))
    assert unregistered == [], (
        f"CDLL loaders missing from common.CTYPES_FILES: {unregistered}"
    )


def test_abi009_fires_on_unregistered_loader(tmp_path):
    """A rogue CDLL call site outside the registry is a finding."""
    from persia_tpu.analysis.common import ctypes_loader_files

    pkg = tmp_path / "persia_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "import ctypes\nlib = ctypes.CDLL('libsomething.so')\n"
    )
    # a docstring/comment mention must NOT count as a loader
    (pkg / "innocent.py").write_text(
        '"""talks about ctypes.CDLL(path) but never calls it"""\n'
        "# lib = ctypes.CDLL(so_path)\n"
    )
    assert ctypes_loader_files(str(tmp_path)) == ["persia_tpu/rogue.py"]
    findings, _cov = abi.check(root=str(tmp_path))
    abi009 = [f for f in findings if f.rule == "ABI009"]
    assert len(abi009) == 1 and abi009[0].path == "persia_tpu/rogue.py"


# ----------------------------------------------------- concurrency fixtures


@pytest.mark.parametrize(
    "fixture, rule",
    [
        ("conc_bare_acquire.py", "CONC001"),
        ("conc_leaky_acquire.py", "CONC002"),
        ("conc_blocking_lock.py", "CONC003"),
        ("conc_inversion.py", "CONC004"),
    ],
)
def test_concurrency_rule_fires(fixture, rule):
    findings = concurrency.check_source(read_text(_fixture(fixture)), fixture)
    assert rule in {f.rule for f in findings}, findings


def test_conc_leaky_acquire_flags_both_permit_and_span():
    findings = concurrency.check_source(
        read_text(_fixture("conc_leaky_acquire.py")), "conc_leaky_acquire.py"
    )
    msgs = [f.message for f in findings if f.rule == "CONC002"]
    assert any("permit" in m for m in msgs)
    assert any("span" in m for m in msgs)


def test_conc_blocking_lock_flags_native_call_too():
    findings = concurrency.check_source(
        read_text(_fixture("conc_blocking_lock.py")), "conc_blocking_lock.py"
    )
    msgs = [f.message for f in findings if f.rule == "CONC003"]
    assert any("time.sleep" in m for m in msgs)
    assert any("native call" in m for m in msgs)


def test_conc_correct_patterns_stay_silent():
    src = (
        "import threading\n"
        "_lock = threading.Lock()\n"
        "sem = threading.Semaphore(2)\n"
        "def ok(out_q, batch):\n"
        "    with _lock:\n"
        "        pass\n"
        "    sem.acquire()\n"
        "    try:\n"
        "        out_q.put(batch)\n"
        "    except Exception:\n"
        "        sem.release()\n"
        "        raise\n"
    )
    assert concurrency.check_source(src, "ok.py") == []


# -------------------------------------------- interprocedural concurrency


@pytest.mark.parametrize(
    "fixture, rule",
    [
        ("conc_transitive_blocking.py", "CONC005"),
        ("conc_cross_inversion.py", "CONC006"),
        ("conc_unranked_lock.py", "CONC007"),
    ],
)
def test_interproc_rule_fires(fixture, rule):
    findings = interproc.check_source(read_text(_fixture(fixture)), fixture)
    assert rule in {f.rule for f in findings}, findings


def test_conc005_reports_call_site_and_chain():
    """The finding anchors on the call made UNDER the lock (refresh's
    line, not _flush's) and names the whole chain plus the blocking leaf;
    the identical call with no lock held stays silent."""
    findings = interproc.check_source(
        read_text(_fixture("conc_transitive_blocking.py")),
        "conc_transitive_blocking.py",
    )
    assert [f.rule for f in findings] == ["CONC005"], findings
    f = findings[0]
    assert "Feeder.refresh -> Feeder._flush" in f.message
    assert "_lock" in f.message and "time.sleep" in f.message


def test_conc006_names_both_locks_and_ranks():
    findings = interproc.check_source(
        read_text(_fixture("conc_cross_inversion.py")), "conc_cross_inversion.py"
    )
    # only the split inversion fires — drain's correctly-ordered lexical
    # nesting is silent here (and ordered, so CONC004 is silent too)
    assert [f.rule for f in findings] == ["CONC006"], findings
    msg = findings[0].message
    assert "_grad_lock" in msg and "_buf_lock" in msg
    assert "WriteBack.accumulate -> WriteBack._stage" in msg


def test_conc007_only_unranked_lock_fires():
    findings = interproc.check_source(
        read_text(_fixture("conc_unranked_lock.py")), "conc_unranked_lock.py"
    )
    assert [f.rule for f in findings] == ["CONC007"], findings
    assert "_stats_lock" in findings[0].message  # _buf_lock is ranked


def test_interproc_suppression_at_call_site():
    # the disable goes on the call under the lock — the leaf may be
    # shared by many callers, each owning its own hold-across decision
    src = (
        "import threading, time\n"
        "_lock = threading.Lock()\n"
        "def leaf():\n"
        "    time.sleep(0.1)\n"
        "def caller():\n"
        "    with _lock:\n"
        "        leaf()  # persia-lint: disable=CONC005\n"
    )
    raw = interproc.check_source(src, "supp.py")
    assert {f.rule for f in raw} == {"CONC005"}
    assert apply_suppressions(raw, {"supp.py": src}) == []


def test_interproc_unknown_receiver_stays_silent():
    # conservative resolution: obj.m() with several candidate classes (or
    # a builtin-container name like .update) must produce no edge, hence
    # no finding — a missed edge is never a false positive
    src = (
        "import threading\n"
        "_lock = threading.Lock()\n"
        "def caller(h, d):\n"
        "    with _lock:\n"
        "        h.update(b'x')\n"  # hashlib, not a repo class
        "        d.flush()\n"
    )
    assert interproc.check_source(src, "silent.py") == []


def test_interproc_callgraph_coverage():
    """The call graph must span at least the ctypes surface the ABI pass
    covers (the ISSUE floor), and resolve a substantial edge set."""
    _index, cov = interproc.build_index(REPO_ROOT)
    assert cov["files"] >= len(CTYPES_FILES)
    assert cov["functions"] > 100
    assert cov["edges"] > 100


# ------------------------------------------------------------- JAX lints


@pytest.mark.parametrize(
    "fixture, rule, n",
    [
        ("jax_host_sync.py", "JAX001", 3),
        ("jax_retrace_branch.py", "JAX002", 2),
        ("jax_donated_reuse.py", "JAX003", 1),
        ("jax_unsynced_timer.py", "JAX004", 1),
    ],
)
def test_jax_rule_fires(fixture, rule, n):
    findings = jax_lint.check_source(
        read_text(_fixture(fixture)), fixture, sync_scope=True, bench_scope=True
    )
    # exactly the seeded violations fire; each fixture's clean twin
    # (guarded_step / good_clip / good_loop / bench_good) stays silent
    assert [f.rule for f in findings] == [rule] * n, findings


def test_jax001_scope_is_hot_paths_only():
    src = read_text(_fixture("jax_host_sync.py"))
    # same source outside parallel// hbm_cache/: JAX001 must stay silent
    findings = jax_lint.check_source(src, "tools/offline_eval.py")
    assert [f.rule for f in findings] == []


@pytest.mark.parametrize(
    "path, fires",
    [
        ("perf/harness.py", True),
        ("perf/entries/b.py", True),
        # a study script beside the benchmark times itself on purpose
        ("perf/limits_one.py", False),
        ("bench.py", False),
    ],
)
def test_jax004_scope_is_the_drivers_timed_path(path, fires):
    src = read_text(_fixture("jax_unsynced_timer.py"))
    findings = jax_lint.check_source(src, path)
    assert ("JAX004" in {f.rule for f in findings}) == fires, findings


def test_jax_suppression_works():
    src = read_text(_fixture("jax_donated_reuse.py")).replace(
        "stale = state + 1.0",
        "stale = state + 1.0  # persia-lint: disable=JAX003",
    )
    raw = jax_lint.check_source(src, "supp.py")
    assert {f.rule for f in raw} == {"JAX003"}
    assert apply_suppressions(raw, {"supp.py": src}) == []


def test_jax004_sees_imported_jit_through_registry():
    """The whole-program half: the jitted callee lives in another module;
    the benchmark's entry only imports it."""
    registry = {"somepkg.kernels.kernel": jax_lint._JitInfo(jitted=True, device=True)}
    src = (
        "import time\n"
        "from somepkg.kernels import kernel\n"
        "def bench(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = kernel(x)\n"
        "    return time.perf_counter() - t0\n"
    )
    findings = jax_lint.check_source(src, "perf/entries/b.py", registry=registry)
    assert [f.rule for f in findings] == ["JAX004"], findings


# ------------------------------------------------------ resilience fixtures


@pytest.mark.parametrize(
    "fixture, rule",
    [
        ("res_raw_sleep.py", "RES001"),
        ("res_raw_timeout.py", "RES002"),
        ("res_adhoc_retry.py", "RES003"),
        ("res_manual_deadline.py", "RES004"),
        ("res_swallow_no_metric.py", "RES005"),
        ("res_single_probe_evict.py", "RES006"),
    ],
)
def test_resilience_rule_fires(fixture, rule):
    findings = resilience_lint.check_source(read_text(_fixture(fixture)), fixture)
    assert rule in {f.rule for f in findings}, findings


def test_res005_metered_or_reraising_loops_are_allowed():
    # counting the failure makes the swallow observable — compliant
    metered = (
        "import logging\n"
        "logger = logging.getLogger(__name__)\n"
        "def watch(poll, m_failed):\n"
        "    while True:\n"
        "        try:\n"
        "            poll()\n"
        "        except Exception as e:\n"
        "            m_failed.inc()\n"
        "            logger.warning('poll failed: %s', e)\n"
    )
    assert resilience_lint.check_source(metered, "metered.py") == []
    # re-raising is not a swallow
    reraising = (
        "def watch(poll):\n"
        "    for _ in range(3):\n"
        "        try:\n"
        "            return poll()\n"
        "        except Exception:\n"
        "            raise\n"
    )
    assert resilience_lint.check_source(reraising, "reraising.py") == []
    # narrow exception classes are a deliberate contract, not a swallow
    narrow = (
        "import logging\n"
        "logger = logging.getLogger(__name__)\n"
        "def watch(poll):\n"
        "    while True:\n"
        "        try:\n"
        "            poll()\n"
        "        except (OSError, ValueError) as e:\n"
        "            logger.warning('transient: %s', e)\n"
    )
    assert resilience_lint.check_source(narrow, "narrow.py") == []


def test_res005_handler_with_state_change_is_allowed():
    # the handler feeds the loop's control state — the failure is acted on
    src = (
        "def drain(fetch):\n"
        "    bad = 0\n"
        "    while True:\n"
        "        try:\n"
        "            fetch()\n"
        "        except Exception:\n"
        "            bad += 1\n"
    )
    assert resilience_lint.check_source(src, "stateful.py") == []


def test_res006_thresholded_eviction_is_allowed():
    # miss accounting in the function makes the eviction a thresholded
    # decision — an N-consecutive-miss detector, not a one-probe reflex
    thresholded = (
        "def watch_replica(client, fleet, idx, miss_streak):\n"
        "    try:\n"
        "        client.healthz()\n"
        "        miss_streak[idx] = 0\n"
        "    except Exception:\n"
        "        miss_streak[idx] += 1\n"
        "        if miss_streak[idx] >= 3:\n"
        "            fleet.remove_replica(idx)\n"
    )
    assert resilience_lint.check_source(thresholded, "thresholded.py") == []
    # a handler that only counts the miss never fires RES006
    counting = (
        "def poll(client, m_miss):\n"
        "    try:\n"
        "        client.healthz()\n"
        "    except Exception:\n"
        "        m_miss.inc()\n"
    )
    assert resilience_lint.check_source(counting, "counting.py") == []
    # eviction without a probe in the try body is out of RES006's scope
    no_probe = (
        "def drop(fleet, idx, load):\n"
        "    try:\n"
        "        load()\n"
        "    except Exception:\n"
        "        fleet.remove_replica(idx)\n"
    )
    assert resilience_lint.check_source(no_probe, "no_probe.py") == []


def test_resilience_policy_driven_loop_is_allowed():
    src = (
        "import time\n"
        "def call_with_retry(pol, deadline, fn):\n"
        "    for attempt in range(3):\n"
        "        try:\n"
        "            return fn()\n"
        "        except ConnectionError:\n"
        "            pass\n"
        "        time.sleep(min(pol.backoff(attempt), deadline.remaining()))\n"
    )
    assert resilience_lint.check_source(src, "engineish.py") == []


def test_inline_suppression_silences_finding():
    path = "res_suppressed.py"
    text = read_text(_fixture(path))
    raw = resilience_lint.check_source(text, path)
    assert {f.rule for f in raw} == {"RES001"}  # the violation IS there
    assert apply_suppressions(raw, {path: text}) == []  # and the disable works


# ------------------------------------------------------ durability fixtures


def test_durability_rule_fires():
    from persia_tpu.analysis import durability

    findings = durability.check_source(
        read_text(_fixture("dur_plain_write.py")), "dur_plain_write.py"
    )
    assert {f.rule for f in findings} == {"DUR001"}
    # the manifest open(), the shard open(), and the np.savez all fire;
    # the read and the non-artifact trace write stay silent
    assert len(findings) == 3, findings


def test_durability_atomic_publish_is_allowed():
    from persia_tpu.analysis import durability

    src = (
        "import json, os, tempfile\n"
        "def save_manifest(path, obj):\n"
        "    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path))\n"
        "    with os.fdopen(fd, 'w') as f:\n"
        "        json.dump(obj, f)\n"
        "        f.flush()\n"
        "        os.fsync(f.fileno())\n"
        "    os.replace(tmp, path + '/MANIFEST.json')\n"
    )
    assert durability.check_source(src, "atomicish.py") == []


def test_durability_suppression_works():
    from persia_tpu.analysis import durability

    src = (
        "def save(path, raw):\n"
        "    with open(path + '/x.ckpt', 'wb') as f:"
        "  # persia-lint: disable=DUR001\n"
        "        f.write(raw)\n"
    )
    raw = durability.check_source(src, "supp.py")
    assert {f.rule for f in raw} == {"DUR001"}
    assert apply_suppressions(raw, {"supp.py": src}) == []


# --------------------------------------------------- observability fixtures


def test_obs001_off_namespace_metric_fires():
    from persia_tpu.analysis import observability_lint

    findings = observability_lint.check_source(
        read_text(_fixture("obs_bad_metric_name.py")), "obs_bad_metric_name.py"
    )
    # the two off-namespace registrations fire; the persia_tpu_ one is clean
    assert [f.rule for f in findings] == ["OBS001", "OBS001"], findings


def test_obs002_manual_stage_timer_fires():
    from persia_tpu.analysis import observability_lint

    findings = observability_lint.check_source(
        read_text(_fixture("obs_manual_timer.py")), "obs_manual_timer.py",
        timer_scope=True,
    )
    # only the raw-clock function fires; the stage_span and metric-.time()
    # flavors are the sanctioned mechanisms
    assert [f.rule for f in findings] == ["OBS002"], findings


def test_obs002_scope_is_pipeline_modules_only():
    from persia_tpu.analysis import observability_lint

    src = read_text(_fixture("obs_manual_timer.py"))
    # same source outside the pipeline scope: OBS002 must stay silent
    # (deadline math in service/resilience.py is RES004's business)
    assert observability_lint.check_source(src, "tools/somescript.py") == []


def test_obs_suppression_works():
    from persia_tpu.analysis import observability_lint

    src = (
        "def reg(m):\n"
        "    return m.counter('requests_total', 'x')"
        "  # persia-lint: disable=OBS001\n"
    )
    raw = observability_lint.check_source(src, "supp.py")
    assert {f.rule for f in raw} == {"OBS001"}
    assert apply_suppressions(raw, {"supp.py": src}) == []


# -------------------------------------------------- control-loop fixtures


def test_ctrl001_unguarded_topology_loop_fires():
    from persia_tpu.analysis import control_lint

    findings = control_lint.check_source(
        read_text(_fixture("ctrl_unguarded_loop.py")), "ctrl_unguarded_loop.py"
    )
    # reshard loop, both scale_serving branches, and the swap loop fire
    assert [f.rule for f in findings] == ["CTRL001"] * 4, findings
    assert {"reshard_ps", "scale_serving", "swap_topology"} <= {
        f.message.split("(")[1].split(")")[0] for f in findings
    }


def test_ctrl001_guarded_and_one_shot_stay_clean():
    from persia_tpu.analysis import control_lint
    from persia_tpu.analysis.common import apply_suppressions as sup

    src = read_text(_fixture("ctrl_guarded_loop.py"))
    raw = control_lint.check_source(src, "ctrl_guarded_loop.py")
    # only the explicitly suppressed loop remains raw; suppression drops it
    assert [f.rule for f in raw] == ["CTRL001"], raw
    assert sup(raw, {"ctrl_guarded_loop.py": src}) == []


def test_ctrl001_for_loop_membership_apply_is_clean():
    from persia_tpu.analysis import control_lint

    # a bounded for over a static list APPLIES a decision — not a control
    # loop (the gateway's bootstrap/probe sweeps)
    src = (
        "def bootstrap(gw, addrs):\n"
        "    for a in addrs:\n"
        "        gw.add_replica(a)\n"
    )
    assert control_lint.check_source(src, "boot.py") == []


def test_ctrl001_skips_test_files():
    from persia_tpu.analysis import control_lint

    findings = control_lint.check(files=[_fixture("ctrl_unguarded_loop.py"),
                                         "tests/test_analysis.py"])
    # fixture dir rides under tests/ → exempt via the tests/ prefix rule
    assert findings == []


def test_ctrl002_unleased_actuation_fires():
    from persia_tpu.analysis import control_lint

    findings = control_lint.check_source_lease(
        read_text(_fixture("ctrl_unleased_actuation.py")),
        "ctrl_unleased_actuation.py",
    )
    # the direct reshard, both heal actuators, and the tier move all fire
    assert [f.rule for f in findings] == ["CTRL002"] * 4, findings
    assert {"reshard_ps", "heal_promote", "heal_drain_gray",
            "apply_migration"} == {
        f.message.split("(")[1].split(")")[0] for f in findings
    }


def test_ctrl002_leased_and_suppressed_stay_clean():
    from persia_tpu.analysis import control_lint
    from persia_tpu.analysis.common import apply_suppressions as sup

    src = read_text(_fixture("ctrl_leased_actuation.py"))
    raw = control_lint.check_source_lease(src, "ctrl_leased_actuation.py")
    # only the explicitly suppressed operator action remains raw — the
    # intent submit and the leased-wrapper closure both carry evidence
    assert [f.rule for f in raw] == ["CTRL002"], raw
    assert sup(raw, {"ctrl_leased_actuation.py": src}) == []


def test_ctrl002_mechanism_layer_is_exempt():
    from persia_tpu.analysis import control_lint

    # a file that IMPLEMENTS an actuator is the mechanism layer: its
    # internal delegation (promote calling replace_replica, resume
    # calling swap_topology) runs below the lease by construction
    src = (
        "def heal_promote(self, victim, advances):\n"
        "    self.router.replace_replica(victim, object())\n"
        "    return 'addr'\n"
    )
    assert control_lint.check_source_lease(src, "helperish.py") == []


# ------------------------------------------------------------- clean tree


def test_clean_tree_zero_findings_with_full_coverage():
    findings, coverage = run_all()
    assert findings == [], "\n".join(f.format() for f in findings)
    abi_cov = coverage["abi"]
    assert set(abi_cov["libs"]) == set(NATIVE_LIBS)
    assert all(n > 0 for n in abi_cov["libs"].values()), abi_cov["libs"]
    assert len(abi_cov["binding_files"]) == 6
    # every registered ctypes file is inside the scanned python set
    assert sorted(coverage["ctypes_files"]) == sorted(CTYPES_FILES)
    assert len(CTYPES_FILES) == 12
    # the interprocedural pass spans at least the ctypes surface
    cg = coverage["callgraph"]
    assert cg["files"] >= len(CTYPES_FILES)
    assert cg["functions"] > 100 and cg["edges"] > 100


def test_findings_are_rule_sorted():
    """Baseline-diffable contract: output order is (rule, path, line)."""
    findings = interproc.check_source(
        read_text(_fixture("conc_cross_inversion.py")), "conc_cross_inversion.py"
    ) + jax_lint.check_source(
        read_text(_fixture("jax_host_sync.py")), "jax_host_sync.py",
        sync_scope=True,
    )
    findings.sort(key=lambda f: (f.rule, f.path, f.line))
    keys = [(f.rule, f.path, f.line) for f in findings]
    assert keys == sorted(keys)
    assert keys[0][0] == "CONC006" and keys[-1][0] == "JAX001"


def test_cli_exit_codes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ok = subprocess.run(
        [sys.executable, "-m", "persia_tpu.analysis"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "0 finding(s)" in ok.stdout
    bad = subprocess.run(
        [sys.executable, "-m", "persia_tpu.analysis", "--rules", "RES001",
         "--root", REPO_ROOT],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert bad.returncode == 0  # clean tree stays clean under a filter too


def test_cli_json_is_machine_readable():
    import json

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "persia_tpu.analysis", "--json"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["findings"] == []
    assert doc["coverage"]["callgraph"]["files"] >= len(CTYPES_FILES)
    assert doc["coverage"]["python_files_scanned"] > 0


def test_cli_baseline_grandfathers_recorded_findings(tmp_path):
    """--write-baseline records findings; --baseline fails only on NEW
    ones — the preflight's fail-on-regression contract."""
    import json
    import shutil

    # a scan root seeded with one known violation
    root = tmp_path / "repo"
    pkg = root / "persia_tpu" / "service"  # RES scope
    pkg.mkdir(parents=True)
    (root / "persia_tpu" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "svc.py").write_text(
        "import time\n"
        "def poll():\n"
        "    time.sleep(5)\n"  # RES001
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(*extra):
        return subprocess.run(
            [sys.executable, "-m", "persia_tpu.analysis",
             "--rules", "RES", "--root", str(root), *extra],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=240,
        )

    dirty = run()
    assert dirty.returncode == 1 and "RES001" in dirty.stdout
    bl = tmp_path / "baseline.json"
    wrote = run("--write-baseline", str(bl))
    assert wrote.returncode == 0
    assert len(json.loads(bl.read_text())["findings"]) == 1
    # same tree + baseline -> grandfathered, exit 0
    ok = run("--baseline", str(bl))
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "grandfathered" in ok.stderr
    # a NEW violation still fails against the old baseline
    (pkg / "svc2.py").write_text(
        "import time\n"
        "def poll2():\n"
        "    time.sleep(9)\n"
    )
    new = run("--baseline", str(bl))
    assert new.returncode == 1
    assert "svc2.py" in new.stdout and "svc.py:" not in new.stdout
    shutil.rmtree(root)


# --------------------------------------------------- sanitizer build variants


def test_variant_so_path_naming():
    assert _native_build.variant_so_path("/x/libpersia_ps.so", "") == "/x/libpersia_ps.so"
    assert _native_build.variant_so_path("/x/libpersia_ps.so", "asan") == "/x/libpersia_ps.asan.so"
    assert _native_build.variant_so_path("/x/libpersia_ps.so", "ubsan") == "/x/libpersia_ps.ubsan.so"
    assert _native_build.variant_so_path("/x/libpersia_ps.so", "tsan") == "/x/libpersia_ps.tsan.so"


def test_sanitize_variant_env_parsing(monkeypatch):
    monkeypatch.delenv("PERSIA_NATIVE_SANITIZE", raising=False)
    assert _native_build.sanitize_variant() == ""
    monkeypatch.setenv("PERSIA_NATIVE_SANITIZE", "ubsan")
    assert _native_build.sanitize_variant() == "ubsan"
    monkeypatch.setenv("PERSIA_NATIVE_SANITIZE", "ASAN")
    assert _native_build.sanitize_variant() == "asan"
    monkeypatch.setenv("PERSIA_NATIVE_SANITIZE", "TSan")
    assert _native_build.sanitize_variant() == "tsan"
    monkeypatch.setenv("PERSIA_NATIVE_SANITIZE", "msan")
    with pytest.raises(ValueError):
        _native_build.sanitize_variant()


def test_tsan_flags_present():
    flags = _native_build.SANITIZER_FLAGS["tsan"]
    assert "-fsanitize=thread" in flags


_TINY_SRC = (
    "#include <cstdint>\n"
    'extern "C" int64_t tiny_add(int64_t a, int64_t b) { return a + b; }\n'
)
_BASE_FLAGS = ["-O2", "-std=c++17", "-fPIC", "-shared"]


def test_flag_change_invalidates_srchash(tmp_path, monkeypatch):
    """The stale-cache hole the source-only hash left open: same source,
    different flags must recompile."""
    monkeypatch.delenv("PERSIA_NATIVE_SANITIZE", raising=False)
    src = tmp_path / "tiny.cpp"
    src.write_text(_TINY_SRC)
    so = str(tmp_path / "libtiny.so")
    _native_build.build_so(str(src), so, _BASE_FLAGS, logger)
    stamp1 = read_text(so + ".srchash")
    # same flags -> stamp unchanged, no rebuild
    _native_build.build_so(str(src), so, _BASE_FLAGS, logger)
    assert read_text(so + ".srchash") == stamp1
    # a -D define changes semantics without touching the source
    _native_build.build_so(str(src), so, _BASE_FLAGS + ["-DEXTRA=1"], logger)
    assert read_text(so + ".srchash") != stamp1


def test_ubsan_variant_builds_distinct_artifact(tmp_path, monkeypatch):
    src = tmp_path / "tiny.cpp"
    src.write_text(_TINY_SRC)
    so = str(tmp_path / "libtiny.so")
    monkeypatch.delenv("PERSIA_NATIVE_SANITIZE", raising=False)
    vanilla = _native_build.build_so(str(src), so, _BASE_FLAGS, logger)
    monkeypatch.setenv("PERSIA_NATIVE_SANITIZE", "ubsan")
    sanitized = _native_build.build_so(str(src), so, _BASE_FLAGS, logger)
    assert vanilla == so
    assert sanitized == str(tmp_path / "libtiny.ubsan.so")
    assert os.path.exists(vanilla) and os.path.exists(sanitized)
    # distinct stamps: the variant can never satisfy the vanilla freshness
    # check (or vice versa) even though the source bytes are identical
    assert read_text(vanilla + ".srchash") != read_text(sanitized + ".srchash")
    import ctypes

    lib = ctypes.CDLL(sanitized)
    lib.tiny_add.restype = ctypes.c_int64
    lib.tiny_add.argtypes = [ctypes.c_int64, ctypes.c_int64]
    assert lib.tiny_add(40, 2) == 42


def test_native_lock_ranks_match_cache_cpp():
    """The round-14 native mutex registry must track the C++ it documents:
    every ranked field exists in native/cache.cpp on the struct the rank
    names, and the ranks encode the walker's acquisition sequence
    (pool handshake -> shard -> sketch -> ledger) strictly."""
    import os
    import re

    from persia_tpu.analysis.common import REPO_ROOT
    from persia_tpu.analysis.lock_order import LOCK_RANKS, NATIVE_LOCK_RANKS

    src = open(os.path.join(REPO_ROOT, "native", "cache.cpp")).read()
    ranks = []
    for key, rank in NATIVE_LOCK_RANKS.items():
        field, _, owner = key.partition("@")
        if owner:
            body = re.search(
                r"struct %s\b.*?\n};" % re.escape(owner), src, re.S
            )
            assert body, f"struct {owner} gone from cache.cpp"
            assert re.search(
                r"std::mutex\s+%s\b" % re.escape(field), body.group(0)
            ), f"{owner}.{field} is not a mutex field anymore"
        else:
            assert re.search(r"std::mutex\s+%s\b" % re.escape(field), src)
        ranks.append(rank)
    assert ranks == sorted(ranks) and len(set(ranks)) == len(ranks)
    # the native plane sits below every Python lock: no shared names that
    # would make rank_of() ambiguous about which registry it answers from
    assert not set(NATIVE_LOCK_RANKS) & set(LOCK_RANKS)


# ------------------------------------------------- protocol (PROTO001-006)


@pytest.mark.parametrize(
    "fixture, rule, line",
    [
        ("proto_raw_manifest_write.py", "PROTO001", 15),
        ("proto_missing_resume_arm.py", "PROTO003", 15),
        ("proto_unprobed_apply.py", "PROTO004", 7),
        ("proto_unfenced_mutator.py", "PROTO005", 6),
    ],
)
def test_protocol_rule_fires(fixture, rule, line):
    findings = protocol.check_source(read_text(_fixture(fixture)), fixture)
    assert [(f.rule, f.line) for f in findings] == [(rule, line)], findings


def test_proto002_raw_mint_flags_every_sink():
    """The same hand-shifted id reaches BOTH journal sinks — each sink is
    its own replay hazard, so both lines fire."""
    findings = protocol.check_source(
        read_text(_fixture("proto_raw_journal_id.py")), "proto_raw_journal_id.py"
    )
    assert sorted((f.rule, f.line) for f in findings) == [
        ("PROTO002", 8), ("PROTO002", 10)], findings
    assert "journal id" in findings[0].message


def test_proto002_fixture_prover_catches_overlap():
    """Two constructors in one module with bit-identical reachable sets:
    the in-module prover must produce the overlap finding, anchored on the
    untagged constructor."""
    findings = protocol.check_source(
        read_text(_fixture("proto_overlap_ids.py")), "proto_overlap_ids.py"
    )
    assert [(f.rule, f.line) for f in findings] == [("PROTO002", 11)], findings
    assert "OVERLAP" in findings[0].message


def test_protocol_clean_fixture_is_silent():
    assert protocol.check_source(
        read_text(_fixture("proto_clean.py")), "proto_clean.py") == []


def test_protocol_inline_suppression():
    src = read_text(_fixture("proto_unfenced_mutator.py")).replace(
        "return svc.reshard_ps(n)  # BAD: no fence anywhere on the chain",
        "return svc.reshard_ps(n)  # persia-lint: disable=PROTO005",
    )
    raw = protocol.check_source(src, "supp.py")
    assert {f.rule for f in raw} == {"PROTO005"}
    assert apply_suppressions(raw, {"supp.py": src}) == []
