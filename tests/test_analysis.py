"""Shardcheck tests: every lint rule fires on a known-bad fixture (with
file:line), the elaborator flags a deliberately mis-specced model, the
REAL tree lints clean, and the dispatch sanitizer catches a cross-thread
multi-device launch."""
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_resnet_tensorflow_tpu.analysis.lint import (
    run_lint, repo_root)
from distributed_resnet_tensorflow_tpu.analysis.report import (
    Finding, format_findings)

PKG = "distributed_resnet_tensorflow_tpu"


# ---------------------------------------------------------------------------
# known-bad fixture repo: one violation per rule, at a known line
# ---------------------------------------------------------------------------

BAD_PY = '''\
import functools
import os
import sys

import jax


def stray(batch, sharding):
    return jax.device_put(batch, sharding)          # line 9: stray-device-put


@functools.lru_cache(maxsize=8)
def cached(mesh, n):                                # line 13: cached-mesh
    return n


def guard(x):
    assert x is not None                            # line 18: bare-assert
    return x


def leave():
    sys.exit(3)                                     # line 23: exit-code-contract


def tell(writer):
    writer.write_event("made_up_event", {})         # line 27: registry-drift


def build(mesh):
    return mesh


memo = functools.lru_cache(maxsize=None)(build)     # line 34: cached-mesh


def record(span):
    with span("made_up_span"):                      # line 38: registry-drift (span catalog)
        pass


def stall_the_loop(f):
    os.fsync(f.fileno())                            # line 43: ckpt-io-thread


def depart():
    rc = 7                                          # line 47: exit-flow literal
    return rc


def relay():
    return depart()


def gone():
    sys.exit(relay())


def slam():
    raise SystemExit(9)                             # line 60: SystemExit literal


def name_the_ops(x):
    with jax.named_scope("made_up_scope"):          # line 64: registry-drift (scope catalog)
        return x + 1


@jax.named_scope("made_up_decorator_scope")         # line 68: the decorator form
def name_them_too(x):
    with jax.named_scope("attention"):              # registered: no finding
        return x
'''

BAD_SH = '''\
#!/bin/bash
python -m distributed_resnet_tensorflow_tpu.main --set trian.batch_size=64
# stale wildcard section reference (typo'd):
#   tune it via --set resilience.watchdogg.*
'''

BAD_MD = '''\
# stale doc
Watch for `{"event": "vanished_event"}` rows.
Spans land via `span("vanished.span")` in the tracer.
Device ops sit under `named_scope("vanished_scope")`, inside `named_scope("moe")`.
'''


@pytest.fixture()
def bad_repo(tmp_path):
    pkg = tmp_path / PKG
    pkg.mkdir()
    (pkg / "bad.py").write_text(BAD_PY)
    (tmp_path / "scripts").mkdir()
    (tmp_path / "scripts" / "bad.sh").write_text(BAD_SH)
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "bad.md").write_text(BAD_MD)
    return str(tmp_path)


def _by_rule(findings):
    out = {}
    for f in findings:
        out.setdefault(f.rule, []).append(f)
    return out


def test_each_rule_fires_with_file_and_line(bad_repo):
    by_rule = _by_rule(run_lint(bad_repo))
    bad_py = os.path.join(PKG, "bad.py")

    f = by_rule["stray-device-put"][0]
    assert (f.path, f.line) == (bad_py, 9)
    cached = {(f.path, f.line) for f in by_rule["cached-mesh"]}
    assert (bad_py, 12) in cached            # decorator form
    assert (bad_py, 34) in cached            # direct-wrap form
    f = by_rule["bare-assert"][0]
    assert (f.path, f.line) == (bad_py, 18)
    exits = {(f.path, f.line) for f in by_rule["exit-code-contract"]}
    assert (bad_py, 23) in exits         # direct sys.exit literal
    assert (bad_py, 47) in exits         # literal flowing out of depart()
    #                                      through relay() into sys.exit
    assert (bad_py, 60) in exits         # raise SystemExit(<literal>)
    assert exits == {(bad_py, 23), (bad_py, 47), (bad_py, 60)}, exits
    drift = {(f.path, f.line) for f in by_rule["registry-drift"]}
    assert (bad_py, 27) in drift                       # undeclared event
    assert (bad_py, 38) in drift                       # undeclared span
    f = by_rule["ckpt-io-thread"][0]
    assert (f.path, f.line) == (bad_py, 43)
    assert (os.path.join("scripts", "bad.sh"), 2) in drift  # bad --set knob
    assert (os.path.join("scripts", "bad.sh"), 4) in drift  # bad wildcard
    assert (os.path.join("docs", "bad.md"), 2) in drift     # stale doc event
    assert (os.path.join("docs", "bad.md"), 3) in drift     # stale doc span


@pytest.mark.parametrize("where,line,name", [
    (os.path.join(PKG, "bad.py"), 64, "made_up_scope"),
    (os.path.join(PKG, "bad.py"), 68, "made_up_decorator_scope"),
    (os.path.join("docs", "bad.md"), 4, "vanished_scope"),
])
def test_registry_drift_rejects_an_unregistered_device_scope(bad_repo, where, line, name):
    """A ``jax.named_scope`` literal (call or decorator) and a scope a doc
    names resolve against ``telemetry.tracer.SCOPE_CATALOG``; a registered
    name (``attention``, ``moe``) on the same lines' neighbours is quiet."""
    scopes = [f for f in run_lint(bad_repo)
              if f.rule == "registry-drift" and "device scope" in f.message]
    assert [(f.path, f.line) for f in scopes if repr(name) in f.message] == [(where, line)]
    assert not any("'attention'" in f.message or "'moe'" in f.message for f in scopes)


def test_suppression_comment_silences_rule(bad_repo):
    path = os.path.join(bad_repo, PKG, "bad.py")
    with open(path) as f:
        src = f.read()
    src = src.replace("assert x is not None",
                      "assert x is not None  # shardcheck: ok(bare-assert)")
    with open(path, "w") as f:
        f.write(src)
    by_rule = _by_rule(run_lint(bad_repo))
    assert "bare-assert" not in by_rule
    # a suppression naming ANOTHER rule must not silence this one
    src = src.replace("# shardcheck: ok(bare-assert)",
                      "# shardcheck: ok(cached-mesh)")
    with open(path, "w") as f:
        f.write(src)
    assert "bare-assert" in _by_rule(run_lint(bad_repo))


def test_stray_device_put_covers_serve_tree(tmp_path):
    """The serving subsystem inherits the transfer invariant: a raw
    ``jax.device_put`` anywhere under serve/ (batcher, swap apply, a future
    request path) is a finding — serve transfers go through
    parallel/sharding.py (put_to_sharding / the CoalescedStager), full stop
    (docs/serving.md; ISSUE: no new raw device_put sites)."""
    pkg = tmp_path / PKG / "serve"
    pkg.mkdir(parents=True)
    (pkg / "rogue.py").write_text(
        "import jax\n\n\ndef apply_swap(tree, shardings):\n"
        "    return jax.device_put(tree, shardings)\n")
    by_rule = _by_rule(run_lint(str(tmp_path)))
    hits = {(f.path, f.line) for f in by_rule.get("stray-device-put", ())}
    assert (os.path.join(PKG, "serve", "rogue.py"), 5) in hits


ROGUE_MODEL = '''\
import jax
import jax.numpy as jnp
import flax.linen as nn


def head(x, hidden):
    x = nn.Dense(hidden)(x)                             # line 7: no dtype
    return jnp.matmul(x, x.T)                           # line 8: no cast


def fine(x, w, dtype):
    y = nn.Dense(4, dtype=dtype)(x)                     # policied: ok
    z = jnp.einsum("ij,jk->ik", y, w.astype(dtype))     # visible cast: ok
    q = jnp.dot(z, w, preferred_element_type=jnp.float32)  # pinned acc: ok
    r = jnp.matmul(q, w)  # shardcheck: ok(unpolicied-matmul)
    return r
'''


def test_unpolicied_matmul_rule(tmp_path):
    """The precision-policy lint (analysis/rules/precision_cast.py): a
    flax module without dtype= and a raw contraction with no visible
    dtype decision are flagged in models/ (file:line); dtype'd /
    preferred_element_type'd / .astype'd / suppressed sites and code
    OUTSIDE models|ops are not."""
    models = tmp_path / PKG / "models"
    models.mkdir(parents=True)
    (models / "rogue.py").write_text(ROGUE_MODEL)
    # the identical code outside the models/ops hot path: out of scope
    (tmp_path / PKG / "elsewhere.py").write_text(ROGUE_MODEL)
    by_rule = _by_rule(run_lint(str(tmp_path)))
    hits = {(f.path, f.line) for f in by_rule.get("unpolicied-matmul", ())}
    rogue = os.path.join(PKG, "models", "rogue.py")
    assert (rogue, 7) in hits
    assert (rogue, 8) in hits
    assert hits == {(rogue, 7), (rogue, 8)}, hits


def test_syntax_error_is_a_finding(tmp_path):
    pkg = tmp_path / PKG
    pkg.mkdir()
    (pkg / "broken.py").write_text("def nope(:\n")
    by_rule = _by_rule(run_lint(str(tmp_path)))
    assert "syntax-error" in by_rule


def test_real_tree_lints_clean():
    findings = run_lint(repo_root())
    assert findings == [], format_findings(findings, verbose=True)


def test_format_findings_groups_by_rule():
    out = format_findings([
        Finding("r1", "a.py", 3, "one"),
        Finding("r2", "b.py", 0, "two"),
        Finding("r1", "a.py", 9, "three"),
    ])
    assert "2 rule(s)" in out and "a.py:3" in out and "b.py: two" in out


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------

def test_event_registry_covers_every_emitted_literal():
    """Every write_event literal in the real tree must be declared — the
    registry-drift rule enforces it, so a clean run implies coverage; this
    pins the registry itself against accidental deletion."""
    from distributed_resnet_tensorflow_tpu.utils.metrics import EVENT_SCHEMAS
    for name in ("input_stages", "corrupt_record", "heartbeat", "straggler",
                 "peer_lost", "peer_failed", "hang", "watchdog_cleared",
                 "watchdog_exit"):
        assert name in EVENT_SCHEMAS
        assert EVENT_SCHEMAS[name]["fields"], name


def test_write_event_warns_once_on_undeclared(tmp_path, caplog):
    from distributed_resnet_tensorflow_tpu.utils import metrics as m
    w = m.MetricsWriter(str(tmp_path), enable_tensorboard=False)
    with caplog.at_level("WARNING"):
        w.write_event("not_a_real_event_xyz", {"a": 1})
        w.write_event("not_a_real_event_xyz", {"a": 2})
        w.write_event("straggler", {"median": 1.0})
    w.close()
    warned = [r for r in caplog.records if "not_a_real_event_xyz" in r.message]
    assert len(warned) == 1          # once, not per row
    rows = m.read_metrics(str(tmp_path))
    assert [r.get("event") for r in rows] == \
        ["not_a_real_event_xyz", "not_a_real_event_xyz", "straggler"]


def test_config_knob_resolution():
    from distributed_resnet_tensorflow_tpu.analysis.rules.registry_drift \
        import _knob_resolves
    assert _knob_resolves("train.batch_size")
    assert _knob_resolves("resilience.watchdog.peer_timeout_secs")
    assert _knob_resolves("resilience.watchdog.*")
    assert _knob_resolves("analysis.dispatch_sanitizer")
    assert not _knob_resolves("trian.batch_size")
    assert not _knob_resolves("train.batch_sizes")
    assert not _knob_resolves("train.batch_size.*")  # leaf is not a section


def test_exit_contract_registry():
    from distributed_resnet_tensorflow_tpu.resilience import (
        EXIT_CONTRACT, FAILURE_EXIT_CODE, INTERRUPT_EXIT_CODE,
        RESUMABLE_EXIT_CODE)
    assert set(EXIT_CONTRACT) == {0, FAILURE_EXIT_CODE,
                                  RESUMABLE_EXIT_CODE, INTERRUPT_EXIT_CODE}
    assert INTERRUPT_EXIT_CODE == 130    # shell convention: 128 + SIGINT


# ---------------------------------------------------------------------------
# elaborator
# ---------------------------------------------------------------------------

def test_spec_checker_flags_misspecced_leaf(mesh8):
    from distributed_resnet_tensorflow_tpu.analysis.elaborate import (
        check_spec_tree)
    shapes = {"w": jax.ShapeDtypeStruct((6, 4), np.float32),
              "b": jax.ShapeDtypeStruct((4,), np.float32)}
    shardings = {"w": NamedSharding(mesh8, P("data")),   # 6 % 8 != 0 — bad
                 "b": NamedSharding(mesh8, P())}
    findings = list(check_spec_tree(shapes, shardings, mesh8, "fixture"))
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "elab-spec" and "'w'" in f.message \
        and "data" in f.message and "(6, 4)" not in f.message
    # rank overflow is its own message
    shardings["b"] = NamedSharding(mesh8, P(None, "data"))
    msgs = [f.message for f in
            check_spec_tree(shapes, shardings, mesh8, "fixture")]
    assert any("rank" in m for m in msgs)


def _tiny_vit_cfg(**model_kw):
    from distributed_resnet_tensorflow_tpu.utils.config import (
        ExperimentConfig, ModelConfig, DataConfig, OptimizerConfig,
        TrainConfig)
    cfg = ExperimentConfig()
    cfg.model = ModelConfig(name="vit", num_classes=10, vit_patch_size=8,
                            vit_dim=32, vit_depth=4, vit_heads=4,
                            compute_dtype="float32",
                            attention_impl="dense", **model_kw)
    cfg.data = DataConfig(dataset="synthetic", image_size=32)
    cfg.optimizer = OptimizerConfig(name="adam", schedule="constant")
    cfg.train = TrainConfig(batch_size=8, train_steps=10)
    return cfg


def test_elaborator_flags_misspecced_model(devices):
    """The deliberately mis-specced fixture: pipeline microbatches that
    cannot divide the local batch — the elaborator must name the train
    step and the divisibility, without touching a device."""
    from distributed_resnet_tensorflow_tpu.analysis.elaborate import (
        elaborate_config)
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig
    cfg = _tiny_vit_cfg(vit_pipeline_microbatches=3)
    cfg.train.batch_size = 8          # local batch 4 over dp=2, 4 % 3 != 0
    findings = elaborate_config(cfg, MeshConfig(data=2, pipeline=2),
                                "fixture@dp_pp")
    rules = {f.rule for f in findings}
    assert "elab-train-step" in rules, format_findings(findings, True)
    msg = next(f for f in findings if f.rule == "elab-train-step").message
    assert "microbatches" in msg


def test_elaborator_clean_on_valid_pipeline_moe(devices):
    """pp×ep MoE elaborates clean — the configuration whose _SpecError
    this subsystem was built to catch (and whose fix it located)."""
    from distributed_resnet_tensorflow_tpu.analysis.elaborate import (
        elaborate_config)
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig
    cfg = _tiny_vit_cfg(vit_num_experts=4, vit_expert_capacity_factor=4.0)
    findings = elaborate_config(
        cfg, MeshConfig(data=2, pipeline=2, expert=2), "fixture@dp_pp_ep")
    assert findings == [], format_findings(findings, verbose=True)


def test_elaborator_clean_on_smoke_preset(devices):
    from distributed_resnet_tensorflow_tpu.analysis.elaborate import (
        run_elaborate)
    findings = run_elaborate(["smoke"])
    assert findings == [], format_findings(findings, verbose=True)


def test_elaborator_traces_serve_step_per_bucket(devices, monkeypatch):
    """The serve/predict step is elaborated per bucket: a predict step
    that cannot trace becomes an elab-serve-step finding naming the
    bucket, instead of a serving replica dying while warming its AOT
    cache (serve/compile_cache.py)."""
    from distributed_resnet_tensorflow_tpu.analysis.elaborate import (
        elaborate_config)
    from distributed_resnet_tensorflow_tpu.train import loop as loop_mod
    from distributed_resnet_tensorflow_tpu.utils.config import (
        MeshConfig, get_preset)

    def broken_predict_step(prep_fn=None, precision=None, apply_fn=None):
        def step(state, batch):
            raise ValueError("serve step fixture breakage")
        return step

    monkeypatch.setattr(loop_mod, "make_predict_step", broken_predict_step)
    cfg = get_preset("smoke")
    cfg.model.resnet_size = 8
    cfg.data.image_size = 8
    findings = elaborate_config(cfg, MeshConfig(data=8), "fixture@dp")
    serve_findings = [f for f in findings if f.rule == "elab-serve-step"]
    assert serve_findings, format_findings(findings, verbose=True)
    assert "bucket" in serve_findings[0].message


def test_check_cli_lint_only():
    from distributed_resnet_tensorflow_tpu.main import main
    with pytest.raises(SystemExit) as e:
        main(["check", "--lint-only"])
    assert e.value.code == 0


# ---------------------------------------------------------------------------
# dispatch sanitizer
# ---------------------------------------------------------------------------

def test_dispatch_sanitizer_catches_cross_thread_launch(mesh8):
    from distributed_resnet_tensorflow_tpu.analysis import (
        dispatch_sanitizer as ds)
    rep = NamedSharding(mesh8, P())
    multi = jax.jit(lambda x: x + 1, out_shardings=rep)
    x = jnp.zeros((8,), jnp.float32)
    multi(x).block_until_ready()      # compile OUTSIDE the guard
    single = jax.jit(lambda x: x * 2)
    single(x).block_until_ready()
    with ds.enabled():
        multi(x).block_until_ready()  # main thread claims ownership
        multi(x).block_until_ready()  # same thread: fine
        errs = []

        def other():
            try:
                multi(x).block_until_ready()
            except Exception as e:    # noqa: BLE001 - collected for assert
                errs.append(e)

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert len(errs) == 1 and \
            isinstance(errs[0], ds.CrossThreadDispatchError)
        assert "consumer thread" in str(errs[0]) or \
            "docs/input_pipeline.md" in str(errs[0])

        # single-device launches are never restricted
        errs2 = []

        def other_single():
            try:
                single(x).block_until_ready()
            except Exception as e:    # noqa: BLE001
                errs2.append(e)

        t2 = threading.Thread(target=other_single)
        t2.start()
        t2.join()
        assert errs2 == []

        # an explicit handoff re-opens ownership
        ds.reset_owner()
        errs3 = []

        def new_owner():
            try:
                multi(x).block_until_ready()
            except Exception as e:    # noqa: BLE001
                errs3.append(e)

        t3 = threading.Thread(target=new_owner)
        t3.start()
        t3.join()
        assert errs3 == []
    assert not ds.is_installed()
    multi(x).block_until_ready()      # uninstalled: unrestricted again


def test_dispatch_sanitizer_config_knob():
    from distributed_resnet_tensorflow_tpu.utils.config import parse_args
    cfg = parse_args(["--preset", "smoke",
                      "--set", "analysis.dispatch_sanitizer=true"])
    assert cfg.analysis.dispatch_sanitizer is True


def test_ckpt_io_rule_scopes_manager_to_writer_fn(tmp_path):
    """Inside checkpoint/manager.py the durability calls are legal ONLY
    within _write (the writer-thread entry); the same call in any other
    method — e.g. a save() that fsyncs on the loop thread — is a
    finding."""
    pkg = tmp_path / PKG / "checkpoint"
    pkg.mkdir(parents=True)
    (pkg / "manager.py").write_text(
        "import os\n\n\n"
        "def _write(step):\n"
        "    os.fsync(step)        # legal: the writer entry\n\n\n"
        "def save(step, f):\n"
        "    os.fsync(f.fileno())  # line 9: loop-thread checkpoint I/O\n")
    by_rule = _by_rule(run_lint(str(tmp_path)))
    hits = {(f.path, f.line) for f in by_rule.get("ckpt-io-thread", ())}
    rel = os.path.join(PKG, "checkpoint", "manager.py")
    assert (rel, 9) in hits
    assert (rel, 5) not in hits


# ---------------------------------------------------------------------------
# unsharded-opt-state rule + elab-zero1 big-mesh sweep (ISSUE 11)
# ---------------------------------------------------------------------------

def _bad_zero1_preset():
    """Fixture preset: optimizer.zero1=on over shapes no 8-way data axis
    divides (35/9/3 logistic) — the promise the rule exists to catch."""
    from distributed_resnet_tensorflow_tpu.utils.config import (
        ExperimentConfig)
    cfg = ExperimentConfig()
    cfg.model.name = "logistic"
    cfg.model.input_size = 35
    cfg.model.hidden_units = 9
    cfg.model.num_classes = 3
    cfg.optimizer.zero1 = "on"
    cfg.optimizer.zero1_min_size = 8
    return cfg


def test_unsharded_opt_state_rule_fires_with_file_and_line(monkeypatch):
    from types import SimpleNamespace
    from distributed_resnet_tensorflow_tpu.analysis.rules import (
        opt_state as rule)
    from distributed_resnet_tensorflow_tpu.utils import config as config_mod
    monkeypatch.setitem(config_mod.PRESETS, "bad_zero1", _bad_zero1_preset)
    findings = [f for f in rule.check(SimpleNamespace(root=repo_root()))
                if "bad_zero1" in f.message]
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "unsharded-opt-state"
    # anchored at the fixture FACTORY's def line in this file
    assert f.path.endswith("test_analysis.py")
    assert f.line == _bad_zero1_preset.__code__.co_firstlineno
    assert "replicated" in f.message


def test_unsharded_opt_state_rule_clean_on_real_presets():
    """The shipped zero1 presets (lars4k/lamb4k) must actually shard —
    the rule passing on the real tree IS the promise check."""
    from types import SimpleNamespace
    from distributed_resnet_tensorflow_tpu.analysis.rules import (
        opt_state as rule)
    assert list(rule.check(SimpleNamespace(root=repo_root()))) == []


def test_elab_zero1_sweep_clean_and_flags_unshardable(devices, monkeypatch):
    """The big-mesh sweep, exercised at the test harness's 8 devices
    (sizes is a parameter; the gate runs 64/256): a real zero1 preset
    elaborates clean, and a preset whose shapes defeat the rule table
    gets an elab-zero1 finding naming the fully-replicated resolution."""
    from distributed_resnet_tensorflow_tpu.analysis.elaborate import (
        run_elaborate_zero1)
    from distributed_resnet_tensorflow_tpu.utils import config as config_mod

    clean = run_elaborate_zero1(["imagenet_resnet50_lars4k"], sizes=(8,))
    assert clean == [], [f.message for f in clean]

    monkeypatch.setitem(config_mod.PRESETS, "bad_zero1", _bad_zero1_preset)
    bad = run_elaborate_zero1(["bad_zero1"], sizes=(8,))
    assert any(f.rule == "elab-zero1" and "FULLY replicated" in f.message
               for f in bad), [f.message for f in bad]
