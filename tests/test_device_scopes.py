"""The device side of the tracing contract: ``telemetry/tracer.SCOPE_CATALOG``
against the ``jax.named_scope`` sites of the package, the benchmark's readers
by scope, docs/observability.md, and ``benchmark/tools/step_parts.py`` on a
hand-built reduction. (The lowered gradient of each decoder family holds the
scopes: tests/test_afmoe.py, tests/test_sdar_moe.py; the lint rejects an
unregistered literal: tests/test_analysis.py.)"""
import ast
import glob
import os

import pytest

from benchmark.tools import step_parts
from distributed_resnet_tensorflow_tpu.telemetry.tracer import SCOPE_CATALOG

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OURS = sorted(n for n, s in SCOPE_CATALOG.items() if s.origin == "scope")
#: the functions of benchmark/flops/afmoe.py that take scope components
BY_SCOPE = ("scope_seconds", "scope_ms_a_step")


def _first_arguments(path: str, called) -> set:
    """String literals among the arguments of the calls ``called`` accepts."""
    with open(path) as f:
        tree = ast.parse(f.read())
    return {a.value for node in ast.walk(tree)
            if isinstance(node, ast.Call) and called(node.func)
            for a in node.args if isinstance(a, ast.Constant) and isinstance(a.value, str)}


def _named(func, names) -> bool:
    return (func.attr if isinstance(func, ast.Attribute) else getattr(func, "id", "")) in names


def _scopes_read_by(reader: str) -> set:
    return _first_arguments(reader, lambda f: _named(f, BY_SCOPE))


READERS = sorted(p for p in glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics", "*.py"))
                 if _scopes_read_by(p))


@pytest.fixture(scope="module")
def emitted():
    found = set()
    for base, _, files in os.walk(os.path.join(ROOT, "distributed_resnet_tensorflow_tpu")):
        for name in files:
            if name.endswith(".py"):
                found |= _first_arguments(os.path.join(base, name),
                                          lambda f: _named(f, ("named_scope",)))
    return found


@pytest.mark.parametrize("name", OURS)
def test_every_scope_of_ours_has_an_emit_site(emitted, name):
    """Nothing in the catalog is dead: each ``jax.named_scope`` it lists is
    put on operations somewhere in the package."""
    assert name in emitted


def test_every_scope_the_package_emits_is_registered(emitted):
    """The registry-drift rule's half, pinned against a catalog that lost
    entries."""
    assert emitted <= set(OURS)


def test_a_scope_lies_under_registered_components():
    for name, scope in SCOPE_CATALOG.items():
        assert scope.origin in ("scope", "module", "jax"), name
        assert all(part in SCOPE_CATALOG for part in scope.under.split("/") if part), name
        assert scope.where and scope.holds and scope.read_by, name


@pytest.mark.parametrize("reader", READERS, ids=lambda p: os.path.basename(p)[:-3])
def test_every_scope_a_reader_names_is_registered(reader):
    """A per-layer metric that reads device time by scope names components
    of the catalog and nothing else: a scope renamed in the program turns
    the lint red before it turns the metric silent."""
    assert _scopes_read_by(reader) <= set(SCOPE_CATALOG)


def test_the_new_readers_by_scope_are_among_them():
    names = {os.path.basename(p)[:-3] for p in READERS}
    assert {"attention_ms", "moe_ms", "lm_head_ms", "moe_experts_roofline",
            "sdar_experts_roofline", "moe_carry_ms", "attention_rest_ms",
            "recompute_ms"} <= names


def test_the_docs_print_the_catalog_row_for_row():
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        page = f.read().split("\n")
    table = step_parts.catalog_table(SCOPE_CATALOG)
    at = page.index(table[0])
    assert page[at:at + len(table)] == table
    assert page[at + len(table)] == ""


# -- step_parts.py on a hand-built reduction ---------------------------------

FWD = "jit(single_step)/jvp(forward)/CausalDecoder/layer1"
BWD = "jit(single_step)/transpose(jvp(forward))/CausalDecoder/jvp(forward)/CausalDecoder/checkpoint"
REDUCED = {"steps": 2, "scope_s": {
    f"{FWD}/attention/attn/q_proj": 0.004,
    f"{FWD}/attention/attn/rotary": 0.002,
    f"{FWD}/attention/attn/core/flash_fwd": 0.006,
    f"{BWD}/rematted_computation/layer1/attention/attn/rotary": 0.002,
    f"{BWD}/layer1/attention/attn/core/flash_bwd_dq": 0.010,
    f"{BWD}/layer1/moe/moe.walked/experts/while/body/carry": 0.008,
    f"{BWD}/layer1/moe/moe.walked/experts/while/body/products/transpose(products)/jvp()": 0.004,
    f"{BWD}/layer1/moe/moe.walked/experts/while/body/to_tokens/while/body": 0.002,
    f"{BWD}/rematted_computation/layer1/moe/moe.walked/shared/up": 0.002,
    f"{BWD}/layer0/mlp/gate": 0.006,
    f"{FWD}/pre_mlp_norm": 0.002,
    "jit(single_step)/jvp(forward)/CausalDecoder/final_norm": 0.002,
    "jit(single_step)/transpose(jvp(forward))/CausalDecoder/lm_head/while/body/"
    "closed_call/checkpoint/rematted_computation": 0.004,
    "jit(single_step)/optimizer": 0.010,
    "": 0.030,
}}


@pytest.mark.parametrize("row,want", [
    ("attention", {"forward": 2.0}),
    ("attention/rotary", {"forward": 1.0, "recomputed": 1.0}),
    ("attention/core", {"forward": 3.0, "backward": 5.0}),
    ("moe/experts/carry", {"backward": 4.0}),
    ("moe/experts/products", {"backward": 2.0}),
    ("moe/experts/to_tokens", {"backward": 1.0}),
    ("moe/shared", {"recomputed": 1.0}),
    ("lm_head", {"recomputed": 2.0}),
    ("optimizer", {"outside": 5.0}),
    ("[layer*/mlp]", {"backward": 3.0}),
    ("[layer*/pre_mlp_norm]", {"forward": 1.0}),
    ("[final_norm]", {"forward": 1.0}),
    ("[no path]", {"outside": 15.0}),
])
def test_step_parts_puts_a_path_in_one_row_and_one_pass(row, want):
    got = step_parts.partition(REDUCED, SCOPE_CATALOG)[row]
    assert {p: ms for p, ms in got.items() if ms} == pytest.approx(want)


def test_step_parts_rows_add_up_to_the_busy_time():
    lines, total = step_parts.table(REDUCED, SCOPE_CATALOG)
    assert total == pytest.approx(1e3 * sum(REDUCED["scope_s"].values()) / 2)
    assert lines[-1].startswith("| **sum** |") and f"**{total:.2f}**" in lines[-1]
    assert len(step_parts.partition(REDUCED, SCOPE_CATALOG)) == 13
