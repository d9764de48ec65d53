"""tpu-lint (client_tpu/analysis): each rule proven against the real bug
it encodes — hit on the known-violation fixture, silent on the clean
twin — plus suppression comments, the baseline ratchet, the CLI gate,
and the requirement that the repo's own tree scans clean."""

import ast
import json
import subprocess
import sys
import threading
import time
from pathlib import Path

from client_tpu.analysis import (
    PROGRAM_REGISTRY,
    REGISTRY,
    all_rules,
    scan_paths,
    scan_source,
)
from client_tpu.analysis import baseline as baseline_mod
from client_tpu.analysis import cache as cache_mod
from client_tpu.analysis import callgraph
from client_tpu.analysis.baseline import filter_findings
from client_tpu.analysis.witness import (
    LockOrderViolation,
    LockWitness,
)

FIXTURES = Path(__file__).parent / "analysis_fixtures"
ROOT = Path(__file__).parent.parent


def _scan(name):
    path = FIXTURES / name
    return scan_source(path.read_text(), str(path))


def _rules_hit(findings):
    return sorted({f.rule for f in findings})


def test_registry_has_all_rules():
    assert set(REGISTRY) >= {
        "NPY-TRUTH", "ASYNC-BLOCK", "LOCK-DISPATCH", "QUEUE-SENTINEL",
        "CV-WAIT-LOOP", "SHARED-MUT", "TIME-WALL", "METRIC-LABEL",
        "RESP-PARAM-OVERWRITE", "BARE-SUPPRESS", "STALE-SUPPRESS",
        "JIT-UNBOUNDED-SHAPE", "REFCOUNT-PAIR", "ACK-BEFORE-STORE",
    }
    assert set(PROGRAM_REGISTRY) >= {
        "LOCK-INV", "BLOCK-UNDER-LOCK", "CALLBACK-UNDER-LOCK",
        "PEER-CALL-UNDER-LOCK", "LOCKSET-RACE",
        "RESOURCE-LEAK", "DOUBLE-RELEASE", "USE-AFTER-RELEASE",
    }
    assert len(all_rules()) >= 18
    for rule in all_rules().values():
        assert rule.rationale  # every rule documents its motivating bug


# -- per-rule hits and misses ---------------------------------------------

def test_npy_truth_hits():
    findings = _scan("npy_truth_bad.py")
    assert _rules_hit(findings) == ["NPY-TRUTH"]
    # membership, remove, if-truthiness, bool(), while-not, assert, plus
    # the cross-method a2654c4 cancel() shape (membership + remove over a
    # numpy-bearing self-attribute, taint visible only in submit)
    assert len(findings) == 8
    cancel_hits = [f for f in findings if "self._pending" in f.message]
    assert len(cancel_hits) >= 2


def test_npy_truth_clean():
    assert _scan("npy_truth_ok.py") == []


def test_async_block_hits():
    findings = _scan("async_block_bad.py")
    assert _rules_hit(findings) == ["ASYNC-BLOCK"]
    # time.sleep, requests.get, self-queue get, local q.get, and the
    # bounded positional block=True put (unbounded puts never block)
    assert len(findings) == 5


def test_async_block_clean():
    assert _scan("async_block_ok.py") == []


def test_lock_dispatch_hits_prefix_admit():
    """The rule is proven against the real pre-fix _admit_locked: both
    jit dispatches under the *_locked convention plus the inline
    with-self._cv tick."""
    findings = _scan("prefix_admit_lock_dispatch.py")
    assert _rules_hit(findings) == ["LOCK-DISPATCH"]
    assert len(findings) == 3
    messages = " ".join(f.message for f in findings)
    assert "self._prefill" in messages
    assert "self._adopt" in messages
    assert "self._tick" in messages


def test_lock_dispatch_clean():
    assert _scan("lock_dispatch_ok.py") == []


def test_queue_sentinel_hits_prefix_cancel():
    """The rule is proven against the real pre-fix cancel(): the
    active-slot branch deactivates without closing the stream queue; the
    release-all path (put in the same branch) stays clean."""
    findings = _scan("prefix_cancel_queue_sentinel.py")
    assert _rules_hit(findings) == ["QUEUE-SENTINEL"]
    assert len(findings) == 1
    assert "slot.active = False" in findings[0].snippet


def test_queue_sentinel_clean():
    assert _scan("queue_sentinel_ok.py") == []


def test_cv_wait_loop_hits():
    findings = _scan("cv_wait_bad.py")
    assert _rules_hit(findings) == ["CV-WAIT-LOOP"]
    assert len(findings) == 1


def test_cv_wait_loop_clean():
    assert _scan("cv_wait_ok.py") == []


def test_shared_mut_hits():
    findings = _scan("shared_mut_bad.py")
    assert _rules_hit(findings) == ["SHARED-MUT"]
    assert len(findings) == 1
    assert "_backlog" in findings[0].message


def test_shared_mut_clean():
    assert _scan("shared_mut_ok.py") == []


def test_shared_mut_pool_hits():
    """Balancer-motivated shape: endpoint-pool health state written from
    request-side methods while the prober thread reads it."""
    findings = _scan("shared_mut_pool_bad.py")
    assert _rules_hit(findings) == ["SHARED-MUT"]
    assert len(findings) == 2
    messages = " ".join(f.message for f in findings)
    assert "_states" in messages and "_draining" in messages


def test_shared_mut_pool_clean():
    assert _scan("shared_mut_pool_ok.py") == []


def test_shared_mut_discovery_hits():
    """Discovery-motivated shape: pool membership mutated IN PLACE
    (append/remove) outside the pool lock while the prober thread
    iterates it — the rule's in-place-mutator extension."""
    findings = _scan("shared_mut_discovery_bad.py")
    assert _rules_hit(findings) == ["SHARED-MUT"]
    assert len(findings) == 2
    messages = " ".join(f.message for f in findings)
    assert "append" in messages and "remove" in messages
    assert "_endpoints" in messages


def test_shared_mut_discovery_clean():
    assert _scan("shared_mut_discovery_ok.py") == []


def test_resp_param_overwrite_hits():
    findings = _scan("resp_param_overwrite_bad.py")
    assert _rules_hit(findings) == ["RESP-PARAM-OVERWRITE"]
    # the subscript-chain stamp (rendered[0]) and the bare-name stamp on
    # a caller-owned response
    assert len(findings) == 2


def test_resp_param_overwrite_clean():
    assert _scan("resp_param_overwrite_ok.py") == []


def test_jit_unbounded_shape_hits():
    """The per-prompt-length prefill recompile shape (pre-serve/lm
    continuous.py): a jitted callable fed a ragged-reshaped request
    array with no pad/bucket sanitizer on the path."""
    findings = _scan("jit_unbounded_shape_bad.py")
    assert _rules_hit(findings) == ["JIT-UNBOUNDED-SHAPE"]
    assert len(findings) == 2  # plain ragged + sanitize-then-re-taint
    assert "pad/bucket" in findings[0].message


def test_jit_unbounded_shape_clean():
    """pad_prompt on the assignment path, inline in the argument list,
    AND rebinding the name to the sanitizer after a ragged reshape
    (last assignment wins) all fix the dispatch shape — no finding."""
    assert _scan("jit_unbounded_shape_ok.py") == []


def test_refcount_pair_hits():
    """The leaked-shared-block shape (serve/lm/kv.py discipline): a class
    that increments a refs/refcount attribute with no decrement anywhere
    — on a mapping (+=) and on a scalar (x = x + 1 rebind)."""
    findings = _scan("refcount_pair_bad.py")
    assert _rules_hit(findings) == ["REFCOUNT-PAIR"]
    assert len(findings) == 2
    messages = " ".join(f.message for f in findings)
    assert "retain()" in messages and "acquire()" in messages
    assert "leaked reference" in findings[0].message


def test_refcount_pair_clean():
    """retain paired with release (the kv.py shape: AugAssign up, BinOp
    subtraction down) and non-refcount counters both stay silent."""
    assert _scan("refcount_pair_ok.py") == []


def test_bg_thread_crash_hits():
    """The silently-dying background thread (the endpoint-pool prober
    incident shape): a Thread-registered service loop whose body can
    raise with no top-level guard — method target AND bare-name target."""
    findings = _scan("bg_thread_crash_bad.py")
    assert _rules_hit(findings) == ["BG-THREAD-CRASH"]
    assert len(findings) == 2
    messages = " ".join(f.message for f in findings)
    assert "_probe_loop()" in messages and "serve_forever()" in messages
    assert "kills the thread silently" in findings[0].message


def test_bg_thread_crash_clean():
    """Guarded shapes stay silent: whole-body try, loop under an outer
    try, the stop.wait sleep shape, bounded for-drivers, loop-less
    one-shot workers."""
    assert _scan("bg_thread_crash_ok.py") == []


def test_span_leak_hits():
    """The leaked-span shapes (the tracing brackets' invariant): a
    sampled span completed on the happy path only, a started timer
    never finished at all, and a profiler tick whose finish sits on
    the happy path only."""
    findings = _scan("span_leak_bad.py")
    assert _rules_hit(findings) == ["SPAN-LEAK"]
    assert len(findings) == 3
    messages = " ".join(f.message for f in findings)
    assert "outside any finally" in messages
    assert "never finishes" in messages
    assert "ptick" in messages


def test_span_leak_clean():
    """try/finally completion, the context-manager form, and both
    ownership transfers (returned / handed to a callee) stay silent."""
    assert _scan("span_leak_ok.py") == []


def test_ack_before_store_hits():
    """Peer replies counted as durability acks without consulting the
    reply's 'stored' field — both the assigned-reply and the
    for-loop-over-_ask shapes (the write-quorum lane's acks-then-loses
    fork)."""
    findings = _scan("ack_before_store_bad.py")
    assert _rules_hit(findings) == ["ACK-BEFORE-STORE"]
    assert len(findings) == 2
    messages = " ".join(f.message for f in findings)
    assert "'stored'" in messages
    assert "reachable" in messages


def test_ack_before_store_clean():
    """'stored'-gated ack counting, transport delivery under a non-ack
    name, and ack bookkeeping with no peer reply in scope all stay
    silent."""
    assert _scan("ack_before_store_ok.py") == []


def test_time_wall_hits():
    findings = _scan("time_wall_bad.py")
    assert _rules_hit(findings) == ["TIME-WALL"]
    # the wall-clock deadline assignment, its comparison, the
    # attribute-expiry assignment, and the annotated-assignment form
    assert len(findings) == 4


def test_time_wall_clean():
    # monotonic deadlines and wall-clock *timestamps* both scan clean
    assert _scan("time_wall_ok.py") == []


def test_metric_label_hits():
    """The rule is proven against the pre-fix serve/metrics.py shape:
    model/version/device names interpolated into label positions without
    the escape helper."""
    findings = _scan("metric_label_bad.py")
    assert _rules_hit(findings) == ["METRIC-LABEL"]
    # one per offending line (core reports one finding per rule+line):
    # the model/version labels f-string and the device-id one
    assert len(findings) == 2
    messages = " ".join(f.message for f in findings)
    assert "model" in messages and "device_id" in messages


def test_metric_label_clean():
    # escape_label()-wrapped label values and non-label interpolations
    # (sample values, metric name suffixes) both scan clean
    assert _scan("metric_label_ok.py") == []


def test_current_metrics_module_passes_metric_label():
    """The post-fix metrics renderer is the motivating module: every label
    value goes through escape_label()."""
    assert scan_paths(
        [str(ROOT / "client_tpu" / "serve" / "metrics.py")]
    ) == []


def test_current_lm_engine_passes_every_rule():
    """The post-fix scheduler is the motivating module: it and its
    stream provider must scan clean (cancel closes active queues; prefill dispatch left the lock)."""
    lm = ROOT / "client_tpu" / "serve" / "lm"
    assert scan_paths([str(lm / "engine.py"), str(lm / "runner.py")]) == []


# -- suppression ----------------------------------------------------------

def test_suppression_comments():
    assert _scan("suppressed_ok.py") == []


def test_suppression_is_per_rule():
    src = (FIXTURES / "cv_wait_bad.py").read_text()
    # waiving a DIFFERENT rule must not silence the finding
    src = src.replace(
        "self._cv.wait()",
        "self._cv.wait()  # tpulint: disable=NPY-TRUTH -- wrong rule",
    )
    findings = scan_source(src, "cv_wait_bad.py")
    assert _rules_hit(findings) == ["CV-WAIT-LOOP"]


def test_parse_error_is_reported():
    findings = scan_source("def broken(:\n", "broken.py")
    assert _rules_hit(findings) == ["PARSE-ERROR"]


# -- baseline ratchet -----------------------------------------------------

def test_baseline_ratchet(tmp_path):
    findings = _scan("prefix_cancel_queue_sentinel.py")
    assert findings
    baseline_path = tmp_path / "baseline.json"
    baseline_mod.save(str(baseline_path), findings)
    counter = baseline_mod.load(str(baseline_path))

    # grandfathered finding passes
    new, old = filter_findings(findings, counter)
    assert new == [] and len(old) == len(findings)

    # a finding NOT in the baseline fails
    extra = _scan("cv_wait_bad.py")
    new, old = filter_findings(findings + extra, counter)
    assert [f.rule for f in new] == ["CV-WAIT-LOOP"]

    # the ratchet never grows: a second occurrence of a baselined line
    # beyond its recorded count is new
    new, old = filter_findings(findings + findings, counter)
    assert len(new) == len(findings) and len(old) == len(findings)


def test_committed_baseline_loads():
    counter = baseline_mod.load(baseline_mod.DEFAULT_BASELINE)
    assert sum(counter.values()) >= 0  # well-formed (possibly empty)


# -- CLI gate -------------------------------------------------------------

def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "client_tpu.analysis", *args],
        cwd=str(ROOT), capture_output=True, text=True, timeout=120,
    )


def test_cli_exits_nonzero_on_findings():
    proc = _cli(
        "tests/analysis_fixtures/prefix_cancel_queue_sentinel.py",
        "tests/analysis_fixtures/prefix_admit_lock_dispatch.py",
        "--no-baseline",
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "QUEUE-SENTINEL" in proc.stdout
    assert "LOCK-DISPATCH" in proc.stdout


def test_cli_repo_tree_is_clean():
    """The acceptance gate: the post-fix tree (sources AND tests) holds
    every invariant the rules encode."""
    proc = _cli("client_tpu", "tests")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_json_output():
    proc = _cli(
        "tests/analysis_fixtures/cv_wait_bad.py", "--json", "--no-baseline"
    )
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["count"] == 1
    assert payload["findings"][0]["rule"] == "CV-WAIT-LOOP"
    assert "CV-WAIT-LOOP" in payload["rules"]


def test_cli_rule_selection_and_catalog():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rule_id in REGISTRY:
        assert rule_id in proc.stdout
    # selecting only an unrelated rule silences the cv finding
    proc = _cli(
        "tests/analysis_fixtures/cv_wait_bad.py", "--rules", "NPY-TRUTH",
        "--no-baseline",
    )
    assert proc.returncode == 0
    proc = _cli("--rules", "NOT-A-RULE")
    assert proc.returncode == 2


def test_cli_missing_path_is_an_error():
    """A typo'd path must fail loudly (exit 2), not scan nothing and
    report a green gate."""
    proc = _cli("no_such_dir_anywhere", "--no-baseline")
    assert proc.returncode == 2
    assert "no such path" in proc.stderr


def test_fixtures_are_excluded_from_tree_scans():
    findings = scan_paths([str(Path("tests"))])
    assert all("analysis_fixtures" not in f.path for f in findings)


def test_write_baseline_rejects_filtered_scans():
    """A --rules- or path-filtered scan must not regenerate the baseline:
    it would silently drop every other rule's grandfathered entries."""
    proc = _cli("client_tpu", "--write-baseline")
    assert proc.returncode == 2
    proc = _cli("--rules", "NPY-TRUTH", "--write-baseline")
    assert proc.returncode == 2


def test_explicitly_named_excluded_dir_is_scanned():
    """Exclusion guards tree walks only: naming the fixtures dir directly
    must scan it (findings, exit 1), not report a silent green no-op."""
    proc = _cli("tests/analysis_fixtures", "--no-baseline")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "QUEUE-SENTINEL" in proc.stdout


# -- whole-program analysis (callgraph + concurrency rules) ----------------

def _pscan(*names):
    """Run per-file AND program rules over the named fixtures."""
    return scan_paths([str(FIXTURES / n) for n in names])


def test_block_under_lock_hits_interprocedural_prefill():
    """The prefill-under-_cv regression, one refactor past what the
    lexical rule can see: the dispatch is two calls below the ``with``.
    LOCK-DISPATCH must MISS it (that is the point of the fixture) and the
    call-graph pass must catch it, plus direct and one-call-deep host
    blocking under the lock."""
    findings = _pscan("block_under_lock_bad.py")
    assert _rules_hit(findings) == ["BLOCK-UNDER-LOCK"]
    assert len(findings) == 3
    messages = " ".join(f.message for f in findings)
    assert "self._prefill" in messages  # the jit dispatch, via the chain
    assert "_admit_one" in messages and "_do_prefill" in messages
    assert "time.sleep" in messages
    # the old lexical rule alone stays silent on this file
    lexical = scan_source(
        (FIXTURES / "block_under_lock_bad.py").read_text(),
        str(FIXTURES / "block_under_lock_bad.py"),
    )
    assert "LOCK-DISPATCH" not in _rules_hit(lexical)


def test_block_under_lock_clean():
    """The post-fix shape (pop under the lock, dispatch outside; cv.wait
    under its own lock) scans clean through every rule family."""
    assert _pscan("block_under_lock_ok.py") == []


def test_peer_call_under_lock_hits_fleet_shapes():
    """The fleet-tier stall: a peer RPC (timeout-bounded, so no blocking
    classifier fires) reached under an engine/pool lock — direct, one
    call below the ``with``, and a rendezvous collective under a pool
    lock.  The blocking rules must stay silent (that is the gap this
    rule closes)."""
    findings = _pscan("peer_call_under_lock_bad.py")
    assert _rules_hit(findings) == ["PEER-CALL-UNDER-LOCK"]
    assert len(findings) == 3
    messages = " ".join(f.message for f in findings)
    assert "prefix_lookup" in messages       # direct, under _cv
    assert "_fetch_remote" in messages       # through the call chain
    assert "cache_lookup" in messages
    assert "all_gather" in messages          # rendezvous collective


def test_peer_call_under_lock_clean():
    """The post-fix shape (snapshot under the lock, peer call outside —
    the serve/lm/engine.py submit/export structure) scans clean through
    every rule family."""
    assert _pscan("peer_call_under_lock_ok.py") == []


def test_lock_inv_hits_abba():
    findings = _pscan("lock_inv_bad.py")
    assert _rules_hit(findings) == ["LOCK-INV"]
    assert len(findings) == 1
    msg = findings[0].message
    assert "Ledger._audit_lock" in msg and "Ledger._write_lock" in msg
    # both witness edges are named, including the one hidden in a call
    assert "Ledger.credit" in msg and "Ledger._audit" in msg


def test_lock_inv_clean():
    assert _pscan("lock_inv_ok.py") == []


def test_callback_under_lock_hits_prefix_delivery():
    """Proven against the pre-fix pool/breaker delivery shape this PR
    fixed: _notify under the private _notify_lock (through the call) and
    a direct observer invocation under the pool lock."""
    findings = _pscan("callback_under_lock_bad.py")
    assert _rules_hit(findings) == ["CALLBACK-UNDER-LOCK"]
    assert len(findings) == 2
    messages = " ".join(f.message for f in findings)
    assert "_notify" in messages
    assert "on_endpoint_state" in messages


def test_callback_under_lock_clean():
    assert _pscan("callback_under_lock_ok.py") == []


def test_program_rules_are_suppressible_with_reason():
    src = (FIXTURES / "lock_inv_bad.py").read_text()
    src = src.replace(
        "with self._audit_lock:\n            with self._write_lock:",
        "with self._audit_lock:\n            # tpulint: disable=LOCK-INV"
        " -- fixture: suppression check\n"
        "            with self._write_lock:",
    )
    path = FIXTURES / "lock_inv_bad.py"
    import tempfile, os  # noqa: E401

    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "lock_inv_suppressed.py")
        with open(p, "w") as fh:
            fh.write(src)
        assert scan_paths([p]) == []
    assert path.exists()  # the real fixture is untouched


def test_callgraph_resolution():
    """self-calls, cross-module imports, constructors, and the unique
    arity-compatible method fallback all resolve; ambiguity does not."""
    src_a = (
        "from pkg_b import helper\n"
        "class A:\n"
        "    def run(self):\n"
        "        self.step()\n"
        "        helper()\n"
        "        B()\n"
        "    def step(self):\n"
        "        pass\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        pass\n"
    )
    src_b = (
        "def helper():\n"
        "    pass\n"
        "class C:\n"
        "    def only_here(self, x):\n"
        "        pass\n"
    )
    mod_a = callgraph.summarize_module(ast.parse(src_a), "pkg_a.py")
    mod_b = callgraph.summarize_module(ast.parse(src_b), "pkg_b.py")
    prog = callgraph.build_program([mod_a, mod_b])
    run = mod_a.functions["A.run"]
    _m, fn = prog.resolve(mod_a, run, ("self", "step"))
    assert fn is not None and fn.qualname == "A.step"
    _m, fn = prog.resolve(mod_a, run, ("name", "helper"))
    assert fn is not None and fn.qualname == "helper"
    _m, fn = prog.resolve(mod_a, run, ("name", "B"))
    assert fn is not None and fn.qualname == "B.__init__"
    # unique-method fallback honors arity (only_here takes exactly one)
    _m, fn = prog.resolve(mod_a, run, ("method", "only_here"), 1)
    assert fn is not None and fn.qualname == "C.only_here"
    _m, fn = prog.resolve(mod_a, run, ("method", "only_here"), 3)
    assert fn is None
    _m, fn = prog.resolve(mod_a, run, ("method", "nowhere"), 0)
    assert fn is None


def test_callgraph_lock_summaries():
    """Held sets, *_locked convention, and deferred Thread targets."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        t = threading.Thread(target=self._loop)\n"
        "    def work(self):\n"
        "        with self._lock:\n"
        "            self.flush_locked()\n"
        "    def flush_locked(self):\n"
        "        pass\n"
        "    def _loop(self):\n"
        "        pass\n"
    )
    mod = callgraph.summarize_module(ast.parse(src), "s.py")
    work = mod.functions["S.work"]
    assert work.acquisitions[0]["lock"] == "S._lock"
    (call,) = [c for c in work.calls if c["ref"] == ("self", "flush_locked")]
    assert call["held"] == ["S._lock"]
    assert mod.functions["S.flush_locked"].requires_lock
    init = mod.functions["S.__init__"]
    deferred = [c for c in init.calls if c["deferred"]]
    assert deferred and deferred[0]["ref"] == ("self", "_loop")
    assert deferred[0]["held"] == []


def test_callgraph_chained_receivers_keep_their_subtrees():
    """A call through a computed receiver (self._factory().dispatch(),
    self._map[0].append(1)) must not swallow the inner call edge or the
    field access riding in the func subtree."""
    src = (
        "class A:\n"
        "    def run(self):\n"
        "        self._factory().dispatch()\n"
        "    def _factory(self):\n"
        "        return self\n"
        "    def use(self):\n"
        "        self._map[0].append(1)\n"
    )
    mod = callgraph.summarize_module(ast.parse(src), "a.py")
    assert [c["ref"] for c in mod.functions["A.run"].calls] == [
        ("self", "_factory")
    ]
    accesses = mod.functions["A.use"].accesses
    assert [(a["attr"], a["deep"]) for a in accesses] == [("_map", True)]


def test_summary_roundtrip_is_lossless():
    src = (FIXTURES / "lock_inv_bad.py").read_text()
    mod = callgraph.summarize_module(ast.parse(src), "lock_inv_bad.py")
    back = callgraph.ModuleSummary.from_dict(
        json.loads(json.dumps(mod.to_dict()))
    )
    assert back.to_dict() == mod.to_dict()


# -- suppression reasons (BARE-SUPPRESS) -----------------------------------

def test_bare_suppress_hits():
    """A reason-less waiver still suppresses its rule but is itself a
    finding — both targeted and blanket forms."""
    findings = _scan("bare_suppress_bad.py")
    assert _rules_hit(findings) == ["BARE-SUPPRESS"]
    assert len(findings) == 2
    messages = " ".join(f.message for f in findings)
    assert "TIME-WALL" in messages and "all rules" in messages


def test_bare_suppress_cannot_waive_itself():
    src = "import time\nx = 1  # tpulint: disable\n"
    findings = scan_source(src, "x.py")
    assert _rules_hit(findings) == ["BARE-SUPPRESS"]


def test_reasoned_suppressions_are_clean():
    assert _scan("suppressed_ok.py") == []


def test_suppression_reason_may_reference_an_issue_number():
    """`-- #1234` is a reason (an audit trail, even): the tail must not
    stop at the first '#'."""
    src = (
        "import time\n"
        "deadline = time.time() + 5"
        "  # tpulint: disable=TIME-WALL -- #1234: wall clock ok here\n"
    )
    assert scan_source(src, "issue_ref.py") == []


def test_docstring_mention_is_not_a_suppression():
    """Prose inside docstrings/strings that mentions the syntax is
    neither a suppression nor a BARE-SUPPRESS finding (tokenizer-based
    comment detection)."""
    src = (
        '"""Docs: waive with `# tpulint: disable=RULE`."""\n'
        'MSG = "x  # tpulint: disable"\n'
    )
    assert scan_source(src, "docs.py") == []


# -- incremental cache ------------------------------------------------------

def test_cache_roundtrip_and_invalidation(tmp_path):
    cache_file = tmp_path / "cache.json"
    target = tmp_path / "mod.py"
    target.write_text(
        (FIXTURES / "lock_inv_bad.py").read_text()
    )
    c1 = cache_mod.AnalysisCache(str(cache_file))
    cold = scan_paths([str(target)], cache=c1)
    assert _rules_hit(cold) == ["LOCK-INV"]
    assert c1.misses >= 1 and cache_file.exists()

    c2 = cache_mod.AnalysisCache(str(cache_file))
    warm = scan_paths([str(target)], cache=c2)
    assert c2.hits == 1 and c2.misses == 0
    assert [f.to_dict() for f in warm] == [f.to_dict() for f in cold]

    # editing the file invalidates its entry
    time.sleep(0.01)
    target.write_text((FIXTURES / "lock_inv_ok.py").read_text())
    c3 = cache_mod.AnalysisCache(str(cache_file))
    fixed = scan_paths([str(target)], cache=c3)
    assert fixed == []
    assert c3.misses == 1


def test_cache_ignored_for_filtered_scans(tmp_path):
    """A --rules-filtered scan must neither read nor poison the cache."""
    cache_file = tmp_path / "cache.json"
    target = tmp_path / "mod.py"
    target.write_text((FIXTURES / "cv_wait_bad.py").read_text())
    c = cache_mod.AnalysisCache(str(cache_file))
    filtered = scan_paths(
        [str(target)], rules={"NPY-TRUTH": REGISTRY["NPY-TRUTH"]},
        cache=c, program_rules={},
    )
    assert filtered == []
    assert not cache_file.exists()  # nothing cached
    full = scan_paths([str(target)], cache=c)
    assert _rules_hit(full) == ["CV-WAIT-LOOP"]


def test_corrupt_cache_degrades_to_full_scan(tmp_path):
    cache_file = tmp_path / "cache.json"
    cache_file.write_text("{not json")
    c = cache_mod.AnalysisCache(str(cache_file))
    target = tmp_path / "mod.py"
    target.write_text((FIXTURES / "cv_wait_bad.py").read_text())
    findings = scan_paths([str(target)], cache=c)
    assert _rules_hit(findings) == ["CV-WAIT-LOOP"]


def test_cache_entry_stored_against_pre_read_stat(tmp_path):
    """The stat key is captured BEFORE the file is read: a save landing
    mid-analysis must leave the entry looking stale (re-scan next run),
    never fresh (which would serve findings for content nobody
    analyzed)."""
    target = tmp_path / "mod.py"
    target.write_text("x = 1\n")
    c = cache_mod.AnalysisCache(str(tmp_path / "cache.json"))
    key = c.stat_key(str(target))
    time.sleep(0.01)
    target.write_text("y = 2  # saved between stat and put\n")
    c.put(str(target), {"findings": []}, key)
    assert c.get(str(target)) is None  # stale → miss → re-scan


def test_absolute_scan_roots_resolve_cross_module_calls(tmp_path):
    """Module identity must match what `import` statements name however
    the scan root is spelled: an absolute CI path and a relative dev path
    produce the same program (and the same interprocedural findings)."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "a.py").write_text(
        "import threading\n"
        "from pkg.b import helper\n\n\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._la = threading.Lock()\n\n"
        "    def go(self):\n"
        "        with self._la:\n"
        "            helper()\n"
    )
    (pkg / "b.py").write_text(
        "import time\n\n\n"
        "def helper():\n"
        "    time.sleep(1.0)\n"
    )
    findings = scan_paths([str(pkg)])  # absolute root
    assert _rules_hit(findings) == ["BLOCK-UNDER-LOCK"]
    assert "A.go -> helper" in findings[0].message


# -- LOCKSET-RACE (Eraser-style lockset inference) -------------------------

def test_lockset_race_hits_live_pre_fix_shapes():
    """Each class freezes one live catch this PR fixed: the unguarded
    cross-root counter (metrics_manager.scrape_errors), the lock-free
    memoization dict iterated caller-side (engine._tick_jits), the
    unguarded late-bind rebind (pre-fix set_registry), and the split
    guard (write under lock A, read under lock B) — reached two calls
    deep, proving the interprocedural chain."""
    findings = _pscan("lockset_race_bad.py")
    races = [f for f in findings if f.rule == "LOCKSET-RACE"]
    fields = sorted(
        f.message.split("field ")[1].split(" ")[0] for f in races
    )
    assert fields == [
        "Publisher.registry", "ScrapeLoop.scrape_errors",
        "SplitGuard._inflight", "TickEngine._jits",
    ]
    # the unguarded-rebind shape is ALSO the lexical SHARED-MUT catch —
    # overlap expected there, and nowhere else
    assert _rules_hit(findings) == ["LOCKSET-RACE", "SHARED-MUT"]
    split = next(f for f in races if "SplitGuard" in f.message)
    # both witness sites ride in the finding: holding sets + root chains
    assert "_stats_lock" in split.message and "_lock" in split.message
    assert "<main>" in split.message and "_loop" in split.message
    assert "SplitGuard.note -> " in split.message  # the chain, not just the site


def test_lockset_race_split_guard_is_invisible_to_shared_mut():
    """The gap the rule closes: every SplitGuard access is lexically
    'under a lock', so the per-file rule cannot see the disjoint guard
    sets."""
    lexical = scan_source(
        (FIXTURES / "lockset_race_bad.py").read_text(),
        str(FIXTURES / "lockset_race_bad.py"),
    )
    assert not any(
        "SplitGuard" in f.message or "_inflight" in f.message
        for f in lexical
    )


def test_lockset_race_clean_twins():
    """Post-fix shapes and every documented exemption (consistent
    guard, safe publication, init-only, single-root, *_locked
    convention) scan clean through every rule family."""
    assert _pscan("lockset_race_ok.py") == []


def test_lockset_race_spawner_writes_are_virgin_phase(tmp_path):
    """Writes in the method that REGISTERS the thread (`start()` spawns
    last — the repo-wide idiom) share __init__'s exemption; the same
    write moved into a post-start method is a finding."""
    template = (
        "import threading\n\n\n"
        "class Srv:\n"
        "    def __init__(self):\n"
        "        self.limit = 0\n\n"
        "    def {method}\n"
        "        self.limit = 8\n{extra}"
        "    def _loop(self):\n"
        "        while True:\n"
        "            try:\n"
        "                if self.limit:\n"
        "                    return\n"
        "            except Exception:\n"
        "                return\n"
    )
    spawner = template.format(
        method="start(self):",
        extra=(
            "        t = threading.Thread(target=self._loop)\n"
            "        t.start()\n\n"
    ))
    late = template.format(
        method="resize(self):",
        extra=(
            "\n    def start(self):\n"
            "        t = threading.Thread(target=self._loop)\n"
            "        t.start()\n\n"
    ))
    from client_tpu.analysis import PROGRAM_REGISTRY as PR

    lockset_only = {"LOCKSET-RACE": PR["LOCKSET-RACE"]}
    p = tmp_path / "srv.py"
    p.write_text(spawner)
    assert scan_paths([str(p)], rules={}, program_rules=lockset_only) == []
    p.write_text(late)
    findings = scan_paths(
        [str(p)], rules={}, program_rules=lockset_only
    )
    assert _rules_hit(findings) == ["LOCKSET-RACE"]
    assert "Srv.limit" in findings[0].message


def test_lockset_race_self_synced_delegate_exemption(tmp_path):
    """Delegating to a lock-OWNING class (the fleet seq_store shape) is
    self-synchronized and silent; the identical delegation to a
    lock-less class is a race."""
    template = (
        "import threading\n\n\n"
        "class Store:\n"
        "    def __init__(self):\n{store_init}"
        "        self._entries = {{}}\n\n"
        "    def get(self, k):\n"
        "        return self._entries.get(k)\n\n"
        "    def pop(self, k):\n"
        "        self._entries.pop(k, None)\n\n\n"
        "class Tier:\n"
        "    def __init__(self):\n"
        "        self.store = Store()\n"
        "        t = threading.Thread(target=self._loop)\n"
        "        t.start()\n\n"
        "    def forget(self, k):\n"
        "        self.store.pop(k)\n\n"
        "    def _loop(self):\n"
        "        while True:\n"
        "            try:\n"
        "                self.store.get(0)\n"
        "            except Exception:\n"
        "                return\n"
    )
    from client_tpu.analysis import PROGRAM_REGISTRY as PR

    lockset_only = {"LOCKSET-RACE": PR["LOCKSET-RACE"]}
    p = tmp_path / "tier.py"
    p.write_text(template.format(
        store_init="        self._lock = threading.Lock()\n"
    ))
    assert scan_paths([str(p)], rules={}, program_rules=lockset_only) == []
    p.write_text(template.format(store_init=""))
    findings = scan_paths(
        [str(p)], rules={}, program_rules=lockset_only
    )
    assert _rules_hit(findings) == ["LOCKSET-RACE"]
    assert "Tier.store" in findings[0].message


def test_lockset_race_suppressible_with_reason(tmp_path):
    src = (FIXTURES / "lockset_race_bad.py").read_text()
    src = src.replace(
        "self._jits[n] = object()  # racy: insert outside _cv",
        "self._jits[n] = object()  # tpulint: disable=LOCKSET-RACE"
        " -- fixture: suppression check",
    )
    p = tmp_path / "suppressed.py"
    p.write_text(src)
    findings = scan_paths([str(p)])
    assert not any("TickEngine" in f.message for f in findings)


# -- resource-lifecycle analysis (ownership tracking + leak rules) ----------

def test_resource_leak_hits():
    """The four leak shapes: a lease released only on the ok path, an
    early return between alloc and release, a socket never closed, and —
    the interprocedural case the lexical rules cannot see — a KV
    reservation acquired through a wrapper (`self._fresh` returns
    `alloc`'s result) and then dropped."""
    findings = _pscan("resource_leak_bad.py")
    assert _rules_hit(findings) == ["RESOURCE-LEAK"]
    assert sorted(f.line for f in findings) == [16, 26, 36, 46]
    messages = {f.line: f.message for f in findings}
    assert "only on some paths" in messages[16]
    assert "return path" in messages[26]
    assert "never releases or transfers" in messages[36]
    # the wrapper acquisition is attributed through the call chain
    assert "self._fresh()" in messages[46]
    assert "KV block reservation" in messages[46]


def test_resource_leak_clean():
    """Every safe custody shape — try/finally, release on all try arms,
    `with`, ownership transfer to a storing callee, None-guard, daemon
    thread, started-then-joined thread — scans clean through every rule
    family."""
    assert _pscan("resource_leak_ok.py") == []


def test_double_release_hits():
    """Sequential double release and release-in-body-plus-finally (the
    finally re-runs on the no-raise path) both pair on one path."""
    findings = _pscan("double_release_bad.py")
    assert _rules_hit(findings) == ["DOUBLE-RELEASE"]
    assert sorted(f.line for f in findings) == [18, 27]
    for f in findings:
        assert "twice on one path" in f.message


def test_double_release_clean():
    """Either-or releases (if/else arms, except vs the no-raise path)
    are one release; the path algebra must never pair them."""
    assert _pscan("double_release_ok.py") == []


def test_use_after_release_hits():
    """A freed block index spliced into a table and a read on a closed
    file — both uses on the same sequential path as the release."""
    findings = _pscan("use_after_release_bad.py")
    assert _rules_hit(findings) == ["USE-AFTER-RELEASE"]
    assert sorted(f.line for f in findings) == [16, 23]
    for f in findings:
        assert "after releasing it" in f.message


def test_use_after_release_clean():
    """Release-in-one-arm/use-in-the-other and use-inside-try-with-
    finally-close are the normal hand-off shapes."""
    assert _pscan("use_after_release_ok.py") == []


def test_resource_leak_exception_edge(tmp_path):
    """A release that lives only in the except handler covers only the
    exception edge — the no-raise path walks out with the reservation
    still held; routing the release through a finally covers both."""
    leaky = tmp_path / "leaky.py"
    leaky.write_text(
        "def fetch(pool, n, sink):\n"
        "    blocks = pool.alloc(n)\n"
        "    if blocks is None:\n"
        "        return None\n"
        "    try:\n"
        "        sink.push(n)\n"
        "    except ValueError:\n"
        "        pool.release(blocks)\n"
        "        raise\n"
        "    return n\n"
    )
    findings = scan_paths([str(leaky)])
    assert _rules_hit(findings) == ["RESOURCE-LEAK"]
    assert "only on some paths" in findings[0].message
    fixed = tmp_path / "fixed.py"
    fixed.write_text(
        "def fetch(pool, n, sink):\n"
        "    blocks = pool.alloc(n)\n"
        "    if blocks is None:\n"
        "        return None\n"
        "    try:\n"
        "        sink.push(n)\n"
        "    finally:\n"
        "        pool.release(blocks)\n"
        "    return n\n"
    )
    assert scan_paths([str(fixed)]) == []


def test_resource_transfer_to_storing_callee_is_ownership(tmp_path):
    """Passing the handle to a callee that stores it on self is a
    custody transfer — the caller is off the hook; passing it to a
    callee the program cannot resolve gets the same benefit of the
    doubt (FN over FP)."""
    mod = tmp_path / "transfer.py"
    mod.write_text(
        "from somewhere import ship_out\n\n\n"
        "class Table:\n"
        "    def adopt(self, blocks):\n"
        "        self._rows = blocks\n\n"
        "    def admit(self, pool, n):\n"
        "        blocks = pool.alloc(n)\n"
        "        if blocks is None:\n"
        "            return\n"
        "        self.adopt(blocks)\n\n\n"
        "def export(pool, n):\n"
        "    blocks = pool.alloc(n)\n"
        "    if blocks is None:\n"
        "        return\n"
        "    ship_out(blocks)\n"
    )
    assert scan_paths([str(mod)]) == []


def test_wrapper_acquired_span_leak_is_interprocedural(tmp_path):
    """A span acquired through a helper (`return tracer.sample(...)`)
    and never completed: the lexical SPAN-LEAK rule cannot see through
    the call, the ownership engine can."""
    mod = tmp_path / "spans.py"
    mod.write_text(
        "def span_for(tracer, name):\n"
        "    return tracer.sample(name)\n\n\n"
        "def handle(tracer, payload):\n"
        "    span = span_for(tracer, 'handle')\n"
        "    return len(payload)\n"
    )
    findings = scan_paths([str(mod)])
    assert "RESOURCE-LEAK" in _rules_hit(findings)
    assert any("span_for()" in f.message for f in findings)
    fixed = tmp_path / "spans_ok.py"
    fixed.write_text(
        "def span_for(tracer, name):\n"
        "    return tracer.sample(name)\n\n\n"
        "def handle(tracer, payload):\n"
        "    span = span_for(tracer, 'handle')\n"
        "    try:\n"
        "        return len(payload)\n"
        "    finally:\n"
        "        span.complete(ok=True)\n"
    )
    assert scan_paths([str(fixed)]) == []


# -- STALE-SUPPRESS (waiver audit) ------------------------------------------

def test_stale_suppress_hits():
    """A waiver outliving its hazard is a finding: the fixed-long-ago
    TIME-WALL waiver, the half-stale multi-rule list (only the dead id
    reported), and a blanket waiver over nothing."""
    findings = _pscan("stale_suppress_bad.py")
    assert _rules_hit(findings) == ["STALE-SUPPRESS"]
    assert len(findings) == 3
    messages = " ".join(f.message for f in findings)
    assert "TIME-WALL" in messages
    assert "NPY-TRUTH" in messages
    assert "any rule" in messages
    # the comment line rides as the snippet: distinct stale waivers in
    # one file stay distinct under the baseline's (path, rule, snippet)
    # key
    assert all(f.snippet for f in findings)
    assert len({f.key() for f in findings}) == 3


def test_stale_suppress_clean_when_waivers_fire():
    assert _pscan("stale_suppress_ok.py") == []


def test_stale_suppress_needs_full_scan():
    """scan_source (one file, per-file rules only) and --rules-filtered
    runs cannot tell 'unused' from 'unchecked': STALE-SUPPRESS only
    reports on full scans."""
    src = (FIXTURES / "stale_suppress_bad.py").read_text()
    assert "STALE-SUPPRESS" not in _rules_hit(
        scan_source(src, "stale_suppress_bad.py")
    )
    filtered = scan_paths(
        [str(FIXTURES / "stale_suppress_bad.py")],
        rules={"TIME-WALL": REGISTRY["TIME-WALL"]}, program_rules={},
    )
    assert "STALE-SUPPRESS" not in _rules_hit(filtered)


def test_stale_suppress_cannot_waive_itself(tmp_path):
    src = (
        "import time\n\n\n"
        "def f():\n"
        "    # tpulint: disable=STALE-SUPPRESS -- meta-waiver\n"
        "    x = 1  # tpulint: disable=TIME-WALL -- long gone\n"
        "    return x\n"
    )
    p = tmp_path / "meta.py"
    p.write_text(src)
    findings = scan_paths([str(p)])
    rules = [f.rule for f in findings]
    # the TIME-WALL waiver is stale AND the meta-waiver (which fired on
    # nothing it may waive) is itself stale — neither can hide
    assert rules.count("STALE-SUPPRESS") == 2


def test_stale_suppress_quoting_prose_is_not_a_directive():
    """A comment QUOTING the syntax mid-text (like the analyzer's own
    docs) is neither a suppression nor stale — the directive must start
    the comment."""
    src = (
        "# usage: waive with `# tpulint: disable=NPY-TRUTH -- why`\n"
        "x = 1\n"
    )
    assert scan_source(src, "docs.py") == []


# -- whole-program pass cache (fileset digest) ------------------------------

def test_program_pass_cached_under_fileset_digest(tmp_path):
    """Touch nothing -> per-file AND program results come from cache;
    edit one file -> only that file re-analyzes, the program pass
    reruns (and its verdict tracks the edit)."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "a.py").write_text(
        "import threading\n"
        "from pkg.b import helper\n\n\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._la = threading.Lock()\n\n"
        "    def go(self):\n"
        "        with self._la:\n"
        "            helper()\n"
    )
    (pkg / "b.py").write_text(
        "import time\n\n\n"
        "def helper():\n"
        "    time.sleep(1.0)\n"
    )
    cache_file = tmp_path / "cache.json"

    c1 = cache_mod.AnalysisCache(str(cache_file))
    cold = scan_paths([str(pkg)], cache=c1)
    assert _rules_hit(cold) == ["BLOCK-UNDER-LOCK"]
    assert c1.program_misses == 1

    c2 = cache_mod.AnalysisCache(str(cache_file))
    warm = scan_paths([str(pkg)], cache=c2)
    assert [f.to_dict() for f in warm] == [f.to_dict() for f in cold]
    assert c2.hits == 3 and c2.misses == 0
    assert c2.program_hits == 1 and c2.program_misses == 0

    # edit ONE file: only it re-analyzes; the program pass reruns and
    # its verdict tracks the edit (the blocking callee went bounded)
    time.sleep(0.01)
    (pkg / "b.py").write_text(
        "import time\n\n\n"
        "def helper():\n"
        "    pass\n"
    )
    c3 = cache_mod.AnalysisCache(str(cache_file))
    fixed = scan_paths([str(pkg)], cache=c3)
    assert fixed == []
    assert c3.misses == 1 and c3.hits == 2
    assert c3.program_misses == 1


def test_program_cache_ignored_for_filtered_scans(tmp_path):
    """A --rules-filtered scan must not consume (or poison) the cached
    program verdict."""
    pkg = tmp_path / "mod.py"
    pkg.write_text((FIXTURES / "lock_inv_bad.py").read_text())
    cache_file = tmp_path / "cache.json"
    c1 = cache_mod.AnalysisCache(str(cache_file))
    full = scan_paths([str(pkg)], cache=c1)
    assert _rules_hit(full) == ["LOCK-INV"]
    c2 = cache_mod.AnalysisCache(str(cache_file))
    filtered = scan_paths(
        [str(pkg)], cache=c2,
        program_rules={"LOCK-INV": PROGRAM_REGISTRY["LOCK-INV"]},
    )
    assert _rules_hit(filtered) == ["LOCK-INV"]
    assert c2.program_hits == 0  # filtered scans recompute


# -- dynamic lock-order witness ---------------------------------------------

def test_witness_detects_abba_cycle():
    w = LockWitness()
    a = w.wrap_lock(threading.Lock(), "A")
    b = w.wrap_lock(threading.Lock(), "B")

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    t1 = threading.Thread(target=ab)
    t1.start()
    t1.join()
    t2 = threading.Thread(target=ba)
    t2.start()
    t2.join()
    assert w.cycles()
    try:
        w.assert_acyclic()
    except LockOrderViolation as e:
        assert "A" in str(e) and "B" in str(e)
    else:
        raise AssertionError("cycle not reported")


def test_witness_consistent_order_is_acyclic():
    w = LockWitness()
    a = w.wrap_lock(threading.Lock(), "A")
    b = w.wrap_lock(threading.Lock(), "B")
    for _ in range(3):
        with a:
            with b:
                pass
    edges = w.assert_acyclic()
    assert edges == 1
    assert w.edges()[("A", "B")] == 3


def test_witness_condition_wait_releases_held_entry():
    """cv.wait() drops the cv from the held stack for its duration: a
    peer acquiring other locks while we wait must not create edges from
    the cv we are not actually holding."""
    w = LockWitness()
    cv = w.wrap_condition(threading.Condition(), "CV")
    other = w.wrap_lock(threading.Lock(), "L")
    ready = threading.Event()

    def waiter():
        with cv:
            ready.set()
            # tpulint: disable=CV-WAIT-LOOP -- witness test: one waiter,
            cv.wait(timeout=2)

    t = threading.Thread(target=waiter)
    t.start()
    ready.wait(2)
    with other:
        pass  # runs while the waiter sits in wait(): no held overlap
    with cv:
        cv.notify_all()
    t.join(2)
    w.assert_acyclic()
    assert ("CV", "L") not in w.edges()


def test_witness_installed_scopes_to_client_tpu():
    """The threading patch wraps locks built under client_tpu/ and leaves
    stdlib-internal allocations (queue.Queue, Condition's private RLock)
    raw — the _is_owned compatibility hazard."""
    import queue

    from client_tpu.serve.frontdoor import Coalescer

    w = LockWitness()
    with w.installed():
        co = Coalescer()
        q = queue.Queue()
        local = threading.Lock()  # test file: not under client_tpu/
    assert type(co._lock).__name__ == "WitnessLock"
    assert "frontdoor" in co._lock._name
    assert type(q.mutex).__name__ != "WitnessLock"
    assert type(local).__name__ != "WitnessLock"
    # and a condition built by repo code keeps working end to end
    with w.installed():
        from client_tpu.serve._completion import CompletionObserver

        obs = CompletionObserver()
        ran = []
        obs.watch({}, lambda *instant: ran.append(1))  # host: inline
        obs.close()
    assert ran == [1]
    w.assert_acyclic()


def test_witness_prefix_matches_packages_not_path_substrings(tmp_path):
    """A checkout directory that happens to be NAMED client_tpu (the
    default `git clone` name) must not pull every file under it into
    witness scope — only a real package root (carrying __init__.py)
    counts."""
    def build_lock_in(directory):
        mod = directory / "maker.py"
        mod.write_text("import threading\nlock = threading.Lock()\n")
        ns = {}
        code = compile(mod.read_text(), str(mod), "exec")
        w = LockWitness()
        with w.installed():
            exec(code, ns)
        return ns["lock"]

    checkout = tmp_path / "client_tpu"  # no __init__.py: just a dir
    checkout.mkdir()
    assert type(build_lock_in(checkout)).__name__ != "WitnessLock"

    pkg = tmp_path / "real" / "client_tpu"  # a package root
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    assert type(build_lock_in(pkg)).__name__ == "WitnessLock"


# -- dynamic race witness ---------------------------------------------------

def _racy_pair(witness):
    """A guarded/unguarded class pair whose lock is witness-wrapped (the
    fixture files live outside client_tpu/, so installed()'s automatic
    construction-site wrapping does not apply here)."""
    class Shared:
        def __init__(self):
            self._lock = witness.wrap_lock(threading.Lock(), "Shared._lock")
            self.count = 0

        def bump_locked_path(self):
            with self._lock:
                self.count = self.count + 1

        def bump_unguarded(self):
            self.count = self.count + 1

    return Shared


def _hammer(fn, n=50, threads=3, collect=None):
    from client_tpu.analysis.witness import RaceViolation

    def run():
        try:
            for _ in range(n):
                fn()
        except RaceViolation as exc:
            if collect is not None:
                collect.append(exc)

    ts = [threading.Thread(target=run) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()


def test_race_witness_fires_on_seeded_unguarded_write(tmp_path):
    """The acceptance bullet: a deliberately seeded unguarded write
    raises with BOTH stack traces and dumps to the flight recorder."""
    from client_tpu.analysis.witness import RaceViolation, RaceWitness
    from client_tpu.serve.flight import FlightRecorder

    flight = FlightRecorder(dump_dir=str(tmp_path), name="race-test")
    w = RaceWitness(flight=flight)
    Shared = _racy_pair(w)
    w.watch_class(Shared, guards=("_lock",))
    caught = []
    with w.installed():
        obj = Shared()
        _hammer(obj.bump_unguarded, collect=caught)
    assert caught, "seeded unguarded write did not raise"
    report = str(caught[0])
    assert "Shared.count" in report
    assert "this access:" in report and "prior conflicting access:" in report
    assert report.count("thread ") >= 2  # both stacks, both threads
    # ...and the evidence landed in the flight recorder ring + on disk
    kinds = [r["kind"] for r in flight.snapshot()]
    assert "race_witness_violation" in kinds
    assert flight.dumps and "race-Shared-count" in flight.dumps[0]
    try:
        w.assert_race_free()
    except RaceViolation:
        pass
    else:
        raise AssertionError("assert_race_free stayed green")


def test_race_witness_silent_on_guarded_writes():
    from client_tpu.analysis.witness import RaceWitness

    w = RaceWitness()
    Shared = _racy_pair(w)
    w.watch_class(Shared, guards=("_lock",))
    with w.installed():
        obj = Shared()
        _hammer(obj.bump_locked_path)
    assert w.assert_race_free() > 0  # it watched, and stayed green


def test_race_witness_first_thread_exclusive_exempt():
    """A single thread may write unguarded all day — Eraser's exclusive
    phase; __init__ writes ride the same exemption."""
    from client_tpu.analysis.witness import RaceWitness

    w = RaceWitness()
    Shared = _racy_pair(w)
    w.watch_class(Shared, guards=("_lock",))
    with w.installed():
        obj = Shared()
        for _ in range(100):
            obj.bump_unguarded()
    assert w.assert_race_free() > 0


def test_race_witness_tolerates_published_reads():
    """Guarded rebinds + lock-free reference reads (the post-fix
    set_registry shape): the witness checks the WRITE-side protocol,
    mirroring the static pass's safe-publication exemption."""
    from client_tpu.analysis.witness import RaceWitness

    w = RaceWitness()

    class Published:
        def __init__(self):
            self._lock = w.wrap_lock(threading.Lock(), "P._lock")
            self.ref = None

        def publish(self, value):
            with self._lock:
                self.ref = value

    w.watch_class(Published, guards=("_lock",))
    with w.installed():
        obj = Published()
        t = threading.Thread(
            target=lambda: [obj.publish(i) for i in range(200)]
        )
        t.start()
        for _ in range(200):
            _ = obj.ref  # lock-free reference load: GIL-atomic
        t.join()
    assert w.assert_race_free() > 0


def test_race_witness_decorator_and_restore():
    """@witness_shared costs nothing unarmed; installed() instruments
    the decorated class and restores it exactly on exit."""
    from client_tpu.analysis.witness import RaceWitness, witness_shared

    @witness_shared("_lock")
    class Decorated:
        def __init__(self):
            self._lock = threading.Lock()
            self.value = 0

    before_set = Decorated.__setattr__
    before_get = Decorated.__getattribute__
    w = RaceWitness()
    with w.installed():
        assert Decorated.__setattr__ is not before_set
        obj = Decorated()
        obj.value = 1
        _ = obj.value
    assert Decorated.__setattr__ is before_set
    assert Decorated.__getattribute__ is before_get
    assert w.field_accesses >= 2  # the armed window recorded traffic


def test_race_witness_is_also_the_lock_order_witness():
    """RaceWitness keeps full LockWitness duty: the ABBA cycle is still
    caught while race instrumentation is armed."""
    from client_tpu.analysis.witness import RaceWitness

    w = RaceWitness()
    a = w.wrap_lock(threading.Lock(), "A")
    b = w.wrap_lock(threading.Lock(), "B")

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    for fn in (ab, ba):
        t = threading.Thread(target=fn)
        t.start()
        t.join()
    assert w.cycles()
    assert w.assert_race_free() == 0  # no witnessed fields, no races


def test_chaos_race_invariant_helper():
    """assert_race_witness_clean: green on None/plain LockWitness, red
    once a RaceWitness recorded a violation."""
    from client_tpu.analysis.witness import (
        LockWitness,
        RaceViolation,
        RaceWitness,
    )
    from client_tpu.testing.chaos import assert_race_witness_clean

    assert assert_race_witness_clean(None) == 0
    assert assert_race_witness_clean(LockWitness()) == 0
    w = RaceWitness()
    Shared = _racy_pair(w)
    w.watch_class(Shared, guards=("_lock",))
    caught = []
    with w.installed():
        obj = Shared()
        _hammer(obj.bump_unguarded, collect=caught)
    assert caught
    try:
        assert_race_witness_clean(w)
    except RaceViolation:
        pass
    else:
        raise AssertionError("race violation not surfaced by the invariant")


# -- CLI: format/explain/cache ----------------------------------------------

def test_cli_format_json_and_alias():
    for flags in (("--format", "json"), ("--json",)):
        proc = _cli(
            "tests/analysis_fixtures/cv_wait_bad.py", *flags,
            "--no-baseline", "--no-cache",
        )
        assert proc.returncode == 1
        payload = json.loads(proc.stdout)
        assert payload["count"] == 1
        assert payload["findings"][0]["rule"] == "CV-WAIT-LOOP"


def test_cli_explain():
    proc = _cli("--explain", "LOCK-INV")
    assert proc.returncode == 0
    assert "lock-order" in proc.stdout.lower()
    proc = _cli("--explain", "BLOCK-UNDER-LOCK")
    assert proc.returncode == 0
    assert "prefill" in proc.stdout.lower()
    proc = _cli("--explain", "NOT-A-RULE")
    assert proc.returncode == 2


def test_cli_fails_on_each_seeded_bad_fixture():
    """The acceptance bullet: the gate exits non-zero on every seeded bad
    fixture for the new rule family."""
    for name, rule in (
        ("lock_inv_bad.py", "LOCK-INV"),
        ("block_under_lock_bad.py", "BLOCK-UNDER-LOCK"),
        ("callback_under_lock_bad.py", "CALLBACK-UNDER-LOCK"),
        ("bare_suppress_bad.py", "BARE-SUPPRESS"),
        ("refcount_pair_bad.py", "REFCOUNT-PAIR"),
        ("bg_thread_crash_bad.py", "BG-THREAD-CRASH"),
        ("span_leak_bad.py", "SPAN-LEAK"),
    ):
        proc = _cli(
            f"tests/analysis_fixtures/{name}", "--no-baseline", "--no-cache"
        )
        assert proc.returncode == 1, (name, proc.stdout, proc.stderr)
        assert rule in proc.stdout


def test_cli_program_rule_selection():
    """--rules works across both families."""
    proc = _cli(
        "tests/analysis_fixtures/lock_inv_bad.py", "--rules", "LOCK-INV",
        "--no-baseline", "--no-cache",
    )
    assert proc.returncode == 1
    proc = _cli(
        "tests/analysis_fixtures/lock_inv_bad.py", "--rules", "NPY-TRUTH",
        "--no-baseline", "--no-cache",
    )
    assert proc.returncode == 0


def test_cli_sarif_output():
    """--format sarif: SARIF 2.1.0 with the finding as an error result,
    the rule catalog in the driver, and 1-based columns."""
    proc = _cli(
        "tests/analysis_fixtures/cv_wait_bad.py", "--format", "sarif",
        "--no-baseline", "--no-cache",
    )
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["version"] == "2.1.0"
    run = payload["runs"][0]
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "CV-WAIT-LOOP" in rule_ids and "LOCKSET-RACE" in rule_ids
    (result,) = run["results"]
    assert result["ruleId"] == "CV-WAIT-LOOP"
    assert result["level"] == "error"
    location = result["locations"][0]["physicalLocation"]
    assert location["artifactLocation"]["uri"].endswith("cv_wait_bad.py")
    assert location["region"]["startLine"] >= 1
    assert location["region"]["startColumn"] >= 1


def test_cli_sarif_marks_grandfathered_baseline_state(tmp_path):
    """Baselined findings ride along as level=note with
    baselineState=unchanged so annotators can fold the ratchet debt."""
    # the baseline keys on the path as scanned: generate it through the
    # CLI so the relative spelling matches the gated run below
    proc = _cli(
        "tests/analysis_fixtures/cv_wait_bad.py", "--json",
        "--no-baseline", "--no-cache",
    )
    payload = json.loads(proc.stdout)
    from client_tpu.analysis import Finding

    findings = [Finding(**f) for f in payload["findings"]]
    baseline = tmp_path / "baseline.json"
    baseline_mod.save(str(baseline), findings)
    proc = _cli(
        "tests/analysis_fixtures/cv_wait_bad.py", "--format", "sarif",
        "--baseline", str(baseline), "--no-cache",
    )
    assert proc.returncode == 0  # grandfathered: the gate stays green
    payload = json.loads(proc.stdout)
    (result,) = payload["runs"][0]["results"]
    assert result["level"] == "note"
    assert result["baselineState"] == "unchanged"


def test_cli_changed_only(tmp_path):
    """--changed-only: per-file findings narrow to files changed vs the
    merge base (uncommitted + untracked); committed-clean trees pass
    even when an unchanged file still carries a finding."""
    import os as _os

    repo = tmp_path / "repo"
    repo.mkdir()
    env = dict(
        _os.environ,
        PYTHONPATH=str(ROOT),
        GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
        GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t",
    )

    def git(*args):
        subprocess.run(
            ["git", *args], cwd=str(repo), check=True, env=env,
            capture_output=True, timeout=60,
        )

    def lint(*args):
        return subprocess.run(
            [sys.executable, "-m", "client_tpu.analysis", "pkg",
             "--no-baseline", "--no-cache", *args],
            cwd=str(repo), env=env, capture_output=True, text=True,
            timeout=120,
        )

    pkg = repo / "pkg"
    pkg.mkdir()
    (pkg / "clean.py").write_text("x = 1\n")
    bad = (FIXTURES / "cv_wait_bad.py").read_text()
    git("init", "-q", "-b", "main")
    git("add", ".")
    git("commit", "-qm", "clean seed")

    # an UNTRACKED bad file is in the changed set: the gate fires
    (pkg / "fresh_bad.py").write_text(bad)
    proc = lint("--changed-only")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "CV-WAIT-LOOP" in proc.stdout

    # committed: vs the merge base nothing changed — the pre-commit
    # path goes green even though a full scan still finds it
    git("add", ".")
    git("commit", "-qm", "carries a finding")
    proc = lint("--changed-only")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    proc = lint()
    assert proc.returncode == 1


# -- dynamic resource witness ------------------------------------------------

def _kv_pool():
    from client_tpu.serve.lm.kv import KvBlockPool
    from client_tpu.serve.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=96, dtype="float32",
    )
    return KvBlockPool(cfg, n_blocks=8, block_size=4)


def test_resource_witness_fires_on_leaked_reservation(tmp_path):
    """A KV reservation still live at the checkpoint raises
    ResourceLeakError carrying the acquisition stack, and dumps the
    live-handle table to the attached flight recorder."""
    import pytest

    from client_tpu.analysis.witness import (
        ResourceLeakError,
        ResourceWitness,
    )
    from client_tpu.serve.flight import FlightRecorder

    flight = FlightRecorder(dump_dir=str(tmp_path), name="leak-test")
    witness = ResourceWitness(flight=flight)
    with witness.installed():
        pool = _kv_pool()
        blocks = pool.alloc(2)
        assert blocks is not None  # deliberately never released
        with pytest.raises(ResourceLeakError) as excinfo:
            witness.assert_clean()
        pool.release(blocks)  # drain: outer session audits stay clean
    msg = str(excinfo.value)
    assert "kv-blocks" in msg and "acquired at" in msg
    # the failed checkpoint shipped its own postmortem
    assert flight.dumps
    kinds = [r["kind"] for r in flight.snapshot()]
    assert "resource_witness_leak" in kinds


def test_resource_witness_silent_after_full_release():
    """alloc + retain = two references per block; two releases drain the
    table and the checkpoint passes, returning the acquisition count (so
    callers can assert the witness actually saw traffic)."""
    from client_tpu.analysis.witness import ResourceWitness

    witness = ResourceWitness()
    with witness.installed():
        pool = _kv_pool()
        blocks = pool.alloc(2)
        pool.retain(blocks)
        pool.release(blocks)
        pool.release(blocks)
        assert witness.assert_clean() == 4  # 2 alloc + 2 retain refs


def test_resource_witness_lease_round_trip():
    """An endpoint lease registers on lease() and retires on any of the
    three release verbs; a second (idempotent) release stays lenient."""
    from client_tpu.analysis.witness import ResourceWitness
    from client_tpu.balance.pool import EndpointPool

    witness = ResourceWitness()
    with witness.installed():
        pool = EndpointPool(["a:1", "b:2"])
        lease = pool.lease()
        assert witness.live()
        lease.success()
        assert witness.assert_clean() == 1
        lease.release()  # idempotent re-release: ignored, still clean
        assert witness.assert_clean() == 1


def test_resource_witness_restores_and_ignores_prior_handles():
    """Handles acquired before arming are invisible (their release is a
    no-op in the table), and after installed() exits the patched
    methods are restored — post-restore traffic never registers."""
    from client_tpu.analysis.witness import ResourceWitness

    pool = _kv_pool()
    pre = pool.alloc(1)  # acquired before the witness armed
    witness = ResourceWitness()
    with witness.installed():
        pool.release(pre)  # pre-arming handle: lenient no-op
        assert witness.assert_clean() == 0
    post = pool.alloc(2)  # after restore: invisible
    try:
        assert witness.assert_clean() == 0
    finally:
        pool.release(post)
