"""spkaddlint fixtures: every rule must fire on its violating fixture and
stay silent on the clean twin — the lint's own contract, pinned.

Layer split mirrors the analyzer: AST rules run on synthetic source
strings (no jax needed), jaxpr rules on tiny traced programs, and the CLI
round-trips through a throwaway repo root.
"""
import json
import os

import numpy as np
import pytest

from repro.analysis import ast_rules, findings as F, vmem
from repro.analysis import jaxpr_rules as JR
from repro.analysis.cli import main as cli_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(fs):
    return [f.rule for f in fs]


# ---------------------------------------------------------------------------
# AST rules (SPK1xx): violating fixture vs clean twin
# ---------------------------------------------------------------------------

def test_spk101_direct_sort_fires_outside_sort_home():
    src = "import jax.numpy as jnp\norder = jnp.argsort(keys)\n"
    fs = ast_rules.scan_source(src, "kernels/foo.py")
    assert rules_of(fs) == ["SPK101"]
    assert fs[0].line == 2 and "stable_argsort" in fs[0].fixit


def test_spk101_silent_inside_sort_home_and_on_routed_sort():
    direct = "import jax.numpy as jnp\norder = jnp.argsort(keys)\n"
    assert ast_rules.scan_source(direct, "core/sparse.py") == []
    routed = ("from repro.core.sparse import stable_argsort\n"
              "order = stable_argsort(keys)\n")
    assert ast_rules.scan_source(routed, "kernels/foo.py") == []


def test_spk101_alias_cannot_dodge_the_rule():
    src = ("from jax.numpy import argsort as innocent_name\n"
           "order = innocent_name(keys)\n")
    assert rules_of(ast_rules.scan_source(src, "core/engine.py")) == ["SPK101"]


def test_spk102_experimental_import_fires_outside_compat():
    for src in ("from jax.experimental import pallas as pl\n",
                "import jax.experimental.pallas\n",
                "from jax.experimental.shard_map import shard_map\n"):
        fs = ast_rules.scan_source(src, "kernels/foo.py")
        assert rules_of(fs) == ["SPK102"], src
    assert ast_rules.scan_source(
        "from jax.experimental import pallas\n", "compat.py") == []


def test_spk103_global_counter_fires_outside_obs():
    src = "def bump():\n    global _calls\n    _calls += 1\n"
    fs = ast_rules.scan_source(src, "core/engine.py")
    assert rules_of(fs) == ["SPK103"]
    assert "obs.metrics" in fs[0].message
    assert ast_rules.scan_source(src, "obs/metrics.py") == []


def test_spk104_span_must_be_with_context_at_launch_boundary():
    bare = "from repro import obs\nspan = obs.span('x')\nspan.close()\n"
    fs = ast_rules.scan_source(bare, "core/engine.py")
    assert rules_of(fs) == ["SPK104"]
    assert "with" in fs[0].message

    misplaced = "from repro import obs\nwith obs.span('x'):\n    pass\n"
    fs = ast_rules.scan_source(misplaced, "core/sparse.py")
    assert rules_of(fs) == ["SPK104"]
    assert "not a launch boundary" in fs[0].message

    good = "from repro import obs\nwith obs.span('x'):\n    pass\n"
    assert ast_rules.scan_source(good, "core/engine.py") == []


def test_spk105_host_nondeterminism_fires_in_traced_dirs_only():
    src = "import time\nt0 = time.perf_counter()\n"
    fs = ast_rules.scan_source(src, "kernels/ops_helper.py")
    assert rules_of(fs) == ["SPK105"]
    assert ast_rules.scan_source(src, "launch/bench.py") == []
    rnd = "import random\nx = random.random()\n"
    assert rules_of(ast_rules.scan_source(rnd, "models/foo.py")) == ["SPK105"]


def test_spk106_bare_assert_fires_anywhere_in_src():
    src = "def f(x):\n    assert x > 0, 'bad'\n    return x\n"
    for rel in ("core/engine.py", "kernels/foo.py", "runtime/delta_sync.py"):
        fs = ast_rules.scan_source(src, rel)
        assert rules_of(fs) == ["SPK106"], rel
        assert fs[0].line == 2 and "python -O" in fs[0].message


def test_spk106_silent_on_raise_twin_and_waivable():
    good = ("def f(x):\n"
            "    if not x > 0:\n"
            "        raise ValueError('bad')\n"
            "    return x\n")
    assert ast_rules.scan_source(good, "core/engine.py") == []
    waived = ("def f(x):\n"
              "    assert x > 0  # spkaddlint: disable=SPK106\n")
    fs = ast_rules.scan_source(waived, "core/engine.py")
    assert rules_of(fs) == ["SPK106"] and fs[0].waived
    assert F.active(fs) == []


def test_spk107_unbounded_probe_loop_fires_in_hash_kernels():
    src = ("import jax\n"
           "import jax.numpy as jnp\n"
           "def probe(h0):\n"
           "    def cond(carry):\n"
           "        h, done = carry\n"
           "        return jnp.logical_not(done)\n"  # no bound compare
           "    def body(carry):\n"
           "        h, _ = carry\n"
           "        return h + 1, h > 4\n"
           "    return jax.lax.while_loop(cond, body, (h0, False))\n")
    fs = ast_rules.scan_source(src, "kernels/hash_slide.py")
    assert rules_of(fs) == ["SPK107"]
    assert "bounded-termination" in fs[0].message
    # same source outside the hash-kernel family: out of scope
    assert ast_rules.scan_source(src, "kernels/partition.py") == []


def test_spk107_unresolvable_cond_fires():
    src = ("import jax\n"
           "from somewhere import opaque_cond\n"
           "jax.lax.while_loop(opaque_cond, lambda c: c, (0,))\n")
    fs = ast_rules.scan_source(src, "kernels/hash_accum.py")
    assert rules_of(fs) == ["SPK107"]
    assert "not statically resolvable" in fs[0].message


def test_spk107_silent_on_bounded_probe_twin():
    good = ("import jax\n"
            "import jax.numpy as jnp\n"
            "def probe(h0, table_size):\n"
            "    def cond(carry):\n"
            "        h, steps, done = carry\n"
            "        return jnp.logical_not(done) & (steps < table_size)\n"
            "    def body(carry):\n"
            "        h, steps, _ = carry\n"
            "        return h + 1, steps + 1, h > 4\n"
            "    return jax.lax.while_loop(cond, body, (h0, 0, False))\n")
    assert ast_rules.scan_source(good, "kernels/hash_slide.py") == []
    # lambda conds resolve too
    lam = ("import jax\n"
           "jax.lax.while_loop(lambda c: c[0] < 8, lambda c: (c[0] + 1,), "
           "(0,))\n")
    assert ast_rules.scan_source(lam, "kernels/hash_accum.py") == []


def test_spk107_inline_doubling_loop_fires_outside_helper():
    src = ("def size_table(bound):\n"
           "    size = 1\n"
           "    while size < 2 * bound:\n"
           "        size *= 2\n"
           "    return size\n")
    fs = ast_rules.scan_source(src, "kernels/hash_slide.py")
    assert rules_of(fs) == ["SPK107"]
    assert "hash_table_size" in fs[0].fixit
    # the SAME loop inside the sanctioned helper is the one legal home
    good = src.replace("def size_table", "def hash_table_size")
    assert ast_rules.scan_source(good, "kernels/hash_accum.py") == []


def test_spk108_durable_write_without_staging_fires():
    src = ("def save(journal_path, buf):\n"
           "    with open(journal_path, 'wb') as f:\n"
           "        f.write(buf)\n")
    fs = ast_rules.scan_source(src, "runtime/foo.py")
    assert rules_of(fs) == ["SPK108"]
    assert "os.replace" in fs[0].fixit
    # keyword mode and string-constant paths are caught too
    kw = "f = open('spool/frame_0001.bin', mode='w')\n"
    assert rules_of(ast_rules.scan_source(kw, "serve/foo.py")) == ["SPK108"]
    ckpt = ("import os\n"
            "def snap(d, buf):\n"
            "    with open(os.path.join(d, 'snapshot.bin'), 'ab') as f:\n"
            "        f.write(buf)\n")
    assert rules_of(ast_rules.scan_source(ckpt, "core/x.py")) == ["SPK108"]


def test_spk108_silent_on_atomic_twin_reads_and_plain_paths():
    # the sanctioned discipline: write a .tmp sibling, os.replace it over
    atomic = ("import os\n"
              "def save(journal_path, buf):\n"
              "    tmp = journal_path + '.tmp'\n"
              "    with open(tmp, 'wb') as f:\n"
              "        f.write(buf)\n"
              "    os.replace(tmp, journal_path)\n")
    assert ast_rules.scan_source(atomic, "runtime/foo.py") == []
    # reading a durable path is fine
    read = "buf = open(journal_path, 'rb').read()\n"
    assert ast_rules.scan_source(read, "runtime/foo.py") == []
    # writing a non-durable path is fine
    plain = "open(report_path, 'w').write('x')\n"
    assert ast_rules.scan_source(plain, "launch/foo.py") == []


def test_spk108_waivable_inline():
    src = ("def save(ckpt, buf):\n"
           "    f = open(ckpt, 'wb')  # spkaddlint: disable=SPK108\n")
    fs = ast_rules.scan_source(src, "runtime/foo.py")
    assert rules_of(fs) == ["SPK108"] and fs[0].waived
    assert F.active(fs) == []


def test_syntax_error_is_its_own_finding():
    fs = ast_rules.scan_source("def broken(:\n", "core/foo.py")
    assert rules_of(fs) == ["SPK101"] and "does not parse" in fs[0].message


# ---------------------------------------------------------------------------
# waivers
# ---------------------------------------------------------------------------

def test_waiver_roundtrip_same_line_and_line_above():
    same = ("import jax.numpy as jnp\n"
            "o = jnp.argsort(k)  # spkaddlint: disable=SPK101\n")
    fs = ast_rules.scan_source(same, "kernels/foo.py")
    assert rules_of(fs) == ["SPK101"] and fs[0].waived
    assert F.active(fs) == []

    above = ("import jax.numpy as jnp\n"
             "# spkaddlint: disable=SPK101\n"
             "o = jnp.argsort(k)\n")
    fs = ast_rules.scan_source(above, "kernels/foo.py")
    assert fs[0].waived


def test_waiver_wrong_rule_does_not_apply():
    src = ("import jax.numpy as jnp\n"
           "o = jnp.argsort(k)  # spkaddlint: disable=SPK102\n")
    fs = ast_rules.scan_source(src, "kernels/foo.py")
    assert rules_of(fs) == ["SPK101"] and not fs[0].waived
    assert F.active(fs) == fs


def test_waiver_parsing_lists_and_all():
    src = "x = 1  # spkaddlint: disable=SPK101, SPK105\ny = 2\n"
    w = F.parse_waivers(src)
    assert w == {1: {"SPK101", "SPK105"}}
    assert F.is_waived({3: {"all"}}, 3, "SPKJ204")
    assert F.is_waived({3: {"all"}}, 4, "SPKJ204")  # line above
    assert not F.is_waived({3: {"all"}}, 5, "SPKJ204")


# ---------------------------------------------------------------------------
# jaxpr rules (SPKJ2xx)
# ---------------------------------------------------------------------------

def test_count_sorts_sees_through_jit_nesting():
    import jax
    import jax.numpy as jnp

    def two_sorts(x):
        return jnp.sort(jax.jit(jnp.sort)(x))

    closed = jax.make_jaxpr(two_sorts)(jnp.arange(4.0))
    assert JR.count_sorts(closed) == 2
    assert JR.count_sorts(jax.make_jaxpr(jnp.sort)(jnp.arange(4.0))) == 1


def test_expected_sorts_table():
    assert JR.expected_sorts("tree", 1) == 1
    assert JR.expected_sorts("tree", 5) == 4
    for regime in ("sorted", "spa", "vec", "blocked_spa"):
        assert JR.expected_sorts(regime, 5) == 1


def test_spkj202_catches_i64_reaching_pallas_call():
    import jax
    import jax.numpy as jnp
    from repro import compat

    pl = compat.pallas

    def copy_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...].astype(jnp.float32)

    def launch(idx):
        return pl.pallas_call(
            copy_kernel,
            out_shape=jax.ShapeDtypeStruct((8,), jnp.float32),
            interpret=True)(idx)

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(launch)(np.arange(8, dtype=np.int64))
    fs = JR.index_dtype_findings(closed, "fixture")
    assert rules_of(fs) == ["SPKJ202"]
    assert "int64" in fs[0].message and "astype" in fs[0].fixit

    # clean twin: int32 indices produce no finding
    closed32 = jax.make_jaxpr(launch)(np.arange(8, dtype=np.int32))
    assert JR.index_dtype_findings(closed32, "fixture") == []


def _tiny_schedule():
    # sorted padded stream over mn=512, part_elems=128 (4 parts), chunk=2:
    # keys {0,1} -> (chunk 0, part 0); {130,140} -> (chunk 1, part 1)
    keys = np.array([0, 1, 130, 140], np.int32)
    return dict(keys_sorted=keys, mn=512, part_elems=128, parts=4, chunk=2)


def test_spkj203_legal_tables_pass():
    fs = JR.validate_step_tables(np.array([0, 1]), np.array([0, 1]),
                                 **_tiny_schedule())
    assert fs == []


def test_spkj203_non_monotone_part_table():
    fs = JR.validate_step_tables(np.array([0, 1]), np.array([1, 0]),
                                 **_tiny_schedule())
    msgs = " | ".join(f.message for f in fs)
    assert all(f.rule == "SPKJ203" for f in fs)
    assert "not non-decreasing" in msgs


def test_spkj203_duplicate_step_double_counts():
    fs = JR.validate_step_tables(np.array([0, 0, 1]), np.array([0, 0, 1]),
                                 **_tiny_schedule())
    assert rules_of(fs) == ["SPKJ203"]
    assert "more than once" in fs[0].message


def test_spkj203_dropped_payload():
    fs = JR.validate_step_tables(np.array([0]), np.array([0]),
                                 **_tiny_schedule())
    assert rules_of(fs) == ["SPKJ203"]
    assert "never scheduled" in fs[0].message


def test_spkj203_real_partition_steps_are_legal():
    assert JR.check_step_tables() == []


def test_spkj204_overspilled_geometry_is_flagged():
    fs = vmem.check_launch(
        cap=1 << 16, m=4096, n=4096, part_elems=1 << 23, chunk=1024,
        regime="vec",
        cost_model={"vec_onehot_max_block_elems": float(1 << 40)},
        label="forced-overspill")
    assert rules_of(fs) == ["SPKJ204"]
    assert "exceeds" in fs[0].message


def test_spkj204_default_matrix_is_clean():
    assert vmem.check_all() == []


def test_working_set_formula_matches_runtime():
    from repro.kernels.ops import fold_working_set_bytes
    # both pipeline buffers of the tile + double-buffered input chunks (+
    # the one-hot fold's (rows, chunk) and (128, chunk) round matrices)
    assert fold_working_set_bytes("serial", tile_elems=1024, chunk=256) \
        == 2 * 1024 * 4 + 2 * 256 * 8
    assert fold_working_set_bytes("onehot", tile_elems=1024, chunk=256) \
        == 2 * 1024 * 4 + 2 * 256 * 8 + 256 * (1024 // 128 + 128) * 8
    assert vmem.working_set_bytes("serial", part_elems=1024, chunk=256) \
        == fold_working_set_bytes("serial", tile_elems=1024, chunk=256)


# ---------------------------------------------------------------------------
# CLI round-trip
# ---------------------------------------------------------------------------

def _fake_root(tmp_path, source):
    pkg = tmp_path / "src" / "repro" / "core"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(source)
    return str(tmp_path)


def test_cli_ast_clean_on_shipped_tree(capsys):
    rc = cli_main(["--ast", "--root", REPO])
    out = capsys.readouterr().out
    assert rc == 0
    # one sanctioned waiver ships in-tree: stream_service's host-side
    # retry-jitter rng (SPK105) — anything beyond that is a regression
    assert "0 finding(s) (1 waived) — OK" in out


def test_cli_gates_red_and_writes_json(tmp_path, capsys):
    root = _fake_root(tmp_path,
                      "import jax.numpy as jnp\no = jnp.sort(k)\n")
    report = tmp_path / "out" / "findings.json"
    rc = cli_main(["--ast", "--root", root, "--json", str(report)])
    assert rc == 1
    payload = json.loads(report.read_text())
    assert payload["ok"] is False
    assert payload["counts"] == {"SPK101": 1}
    (f,) = payload["findings"]
    assert f["rule"] == "SPK101" and not f["waived"]
    assert f["path"] == "src/repro/core/bad.py" and f["line"] == 2
    assert "FAIL" in capsys.readouterr().out


def test_cli_disable_is_a_global_waiver(tmp_path, capsys):
    root = _fake_root(tmp_path,
                      "import jax.numpy as jnp\no = jnp.sort(k)\n")
    rc = cli_main(["--ast", "--root", root, "--disable", "SPK101"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "(1 waived)" in out and "[waived]" in out


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in F.RULES:
        assert rule in out


def test_shipped_tree_ast_scan_is_clean():
    fs = F.active(ast_rules.scan_tree(os.path.join(REPO, "src", "repro")))
    assert fs == []


# ---------------------------------------------------------------------------
# gate plumbing (satellite: bench_report --gate must fail loudly, not crash)
# ---------------------------------------------------------------------------

def test_missing_baselines_reports_every_tracked_family():
    from repro.obs import ledger
    lines = ledger.missing_baselines([])
    assert len(lines) == len(ledger.TRACKED_ORACLES)
    assert all(line.startswith("NO BASELINE ") for line in lines)


def test_missing_baselines_empty_once_families_observed():
    from repro.obs import ledger
    entries = [{
        "key": {"commit": "c0", "backend": "cpu", "suite": "s",
                "geometry": ""},
        "records": [{"name": "io/64x8/onepass_loads", "value": 3.0},
                    {"name": "smoke/serial_stores", "value": 10.0},
                    {"name": "allreduce/p4/coll_bytes", "value": 128.0},
                    {"name": "chaos/ef/bytes_per_sync", "value": 700.0},
                    {"name": "chaos/ef/catchup_window_max", "value": 4.0},
                    {"name": "hash/er_small/insert_loads", "value": 512.0},
                    {"name": "hash/er_small/probes_per_insert",
                     "value": 1.0},
                    {"name": "stream/steady/p99_flush_latency", "value": 0.7},
                    {"name": "stream/overload/shed_rate", "value": 0.1}],
    }]
    assert ledger.missing_baselines(entries) == []


@pytest.mark.parametrize("regime,k,expected", [
    ("vec", 3, 1), ("tree", 3, 2), ("blocked_spa", 5, 1),
])
def test_one_sort_invariant_spot_check(regime, k, expected):
    """One live cell per regime family — the full matrix runs in the CI
    static lane (scripts/spkaddlint.py --jaxpr); this pins the mechanism."""
    import jax
    from repro.core import engine as E

    mats = JR._collection(11, k, 16, 4, 8)
    force = dict(JR.REGIME_FORCES[regime])
    closed = jax.make_jaxpr(
        lambda: E.spkadd_auto(mats, cost_model=force))()
    assert JR.count_sorts(closed) == expected
    assert JR.index_dtype_findings(closed, f"{regime}") == []
