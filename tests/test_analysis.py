"""Static-analysis tests (ISSUE 6): srlint rule fixtures + jaxpr budgets.

Two halves, both device-free:

- **srlint fixtures**: one known-bad snippet per lint rule, asserting the
  rule fires exactly where expected (file:line) and that its allowlist
  token silences it. Pure AST — no jax.
- **jaxpr budgets**: abstract-trace each engine's step on the pinned 2pc-3
  anchor (`jax.make_jaxpr` over ShapeDtypeStructs — nothing executes) and
  pin the audited per-step HBM bytes / FLOPs / PCIe floor. The ceilings
  have ~25% headroom over the measured r11 values: an edit that
  re-introduces an r8-style full-carry gather (~2x step bytes) fails the
  pin with the op named, while jax-version jitter in jaxpr shape does not.
  The floors catch the opposite failure — a trace that silently collapsed
  (lost its insert chain, traced a stub) and no longer measures the engine.

The whole file is abstract tracing only; tier-1 is timeout-bound at 870 s
and this file budgets ~15 s of it.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from stateright_tpu.analysis.srlint import lint_paths, lint_source

ROOT = Path(__file__).resolve().parent.parent


def _lint(src: str, module: str = "stateright_tpu.tensor.fixture"):
    return lint_source(textwrap.dedent(src), module=module, root=ROOT)


def _rules(findings):
    return [f.rule for f in findings]


# -- SR000: directive hygiene --------------------------------------------------


def test_sr000_unknown_directive_and_missing_reason():
    f = _lint(
        """\
        x = 1  # srlint: hots-ok typo'd token
        y = 2  # srlint: host-ok
        """
    )
    assert _rules(f) == ["SR000", "SR000"]
    assert f[0].line == 1 and "unknown srlint directive" in f[0].message
    assert f[1].line == 2 and "needs a reason" in f[1].message


# -- SR001: host sync inside a step region -------------------------------------

SR001_FIXTURE = """\
import jax
import numpy as np

def step(c):
    k = c.sum().item()
    a = np.asarray(c)
    return c + k

jitted = jax.jit(step)
"""


def test_sr001_host_sync_in_jitted_fn_fires_per_site():
    f = _lint(SR001_FIXTURE)
    assert _rules(f) == ["SR001", "SR001"]
    assert f[0].line == 5 and ".item()" in f[0].message
    assert f[1].line == 6 and "numpy.asarray" in f[1].message
    assert "step" in f[0].message  # names the offending region


def test_init_module_relative_imports_resolve_in_package():
    # `from .registry import REGISTRY` inside stateright_tpu/obs/__init__.py
    # must resolve to stateright_tpu.obs.registry, not stateright_tpu.registry
    # — module_name_for has already stripped "__init__", so the dotted name
    # names the package and level-1 means "here", not the parent. A wrong
    # map silently drops call-graph edges (SR001 false negatives).
    import ast as ast_mod

    from stateright_tpu.analysis.regions import _build_import_map

    tree = ast_mod.parse("from .registry import REGISTRY")
    assert _build_import_map(tree, "stateright_tpu.obs", is_pkg=True) == {
        "REGISTRY": "stateright_tpu.obs.registry.REGISTRY"
    }
    assert _build_import_map(tree, "stateright_tpu.obs.other") == {
        "REGISTRY": "stateright_tpu.obs.registry.REGISTRY"
    }


def test_trailing_annotation_does_not_leak_to_next_line():
    # A trailing `# srlint: host-ok` annotates its own line only; an
    # unannotated host sync on the very next line must still fire (only a
    # STANDALONE comment on the line above allowlists downward).
    f = _lint(
        """\
        import jax

        def step(c):
            k = c.sum().item()  # srlint: host-ok reviewed boundary sync
            j = c.max().item()
            return c + k + j

        jitted = jax.jit(step)
        """
    )
    assert _rules(f) == ["SR001"]
    assert f[0].line == 5


def test_sr001_silent_outside_step_region():
    # The same calls in a plain host function are legal.
    f = _lint(
        """\
        import numpy as np

        def host_only(c):
            return np.asarray(c).item()
        """
    )
    assert f == []


def test_sr001_reaches_while_loop_body_transitively():
    # The body fn is a step-region root via jax.lax.while_loop; the helper
    # it calls is in the region transitively.
    f = _lint(
        """\
        import jax

        def helper(c):
            return float(c[0])

        def body(c):
            return helper(c)

        def run(c0):
            return jax.lax.while_loop(lambda c: c[0] < 3, body, c0)
        """
    )
    assert _rules(f) == ["SR001"]
    assert f[0].line == 4 and "float()" in f[0].message


def test_sr001_host_ok_annotation_silences():
    f = _lint(
        """\
        import jax

        def step(c):
            # srlint: host-ok trace-time shape constant, not a device sync
            k = int(c.shape[0])
            return c + k

        jitted = jax.jit(step)
        """
    )
    assert f == []


# -- SR002: checkpoint writes outside faults/ckptio.py -------------------------


def test_sr002_bare_savez_and_binary_open_fire():
    f = _lint(
        """\
        import numpy as np

        def save(path, table):
            np.savez(path, table=table)
            with open(path, "wb") as fh:
                fh.write(b"x")
        """
    )
    assert _rules(f) == ["SR002", "SR002"]
    assert f[0].line == 4 and "faults/ckptio.py" in f[0].message
    assert f[1].line == 5 and "'wb'" in f[1].message


def test_sr002_catches_np_save_path_open_and_io_open():
    # The obvious siblings of the banned writers must not slip through:
    # np.save, Path(...).open("wb") (mode is the FIRST argument there),
    # and io.open — while a path constant that merely contains 'w' and
    # 'b' ("raw.bin") must not be mistaken for a mode string.
    f = _lint(
        """\
        import io
        import numpy as np
        from pathlib import Path

        def save(path, table):
            np.save(path, table)
            with Path(path).open("wb") as fh:
                fh.write(b"x")
            with io.open(path, "ab") as fh:
                fh.write(b"x")

        def read_only():
            return open("raw.bin").read()
        """
    )
    assert _rules(f) == ["SR002", "SR002", "SR002"]
    assert f[0].line == 6 and "numpy.save" in f[0].message
    assert f[1].line == 7 and "'wb'" in f[1].message
    assert f[2].line == 9 and "'ab'" in f[2].message


def test_sr002_bare_blob_put_fires_outside_the_backend():
    # ISSUE 15 satellite: the BlobStore write surface is SR002 territory
    # exactly like a bare atomic_savez — a put that skips ckptio skips
    # the CRC footer and the epoch fence. Both spellings: the URI helper
    # by (resolved) name, and `.put`/`.put_if_absent` on a blob-shaped
    # receiver. CACHE.put / queue.put stay out of scope.
    f = _lint(
        """\
        from stateright_tpu.faults.blobstore import blob_backend, put_blob

        def publish(uri, data, store_root):
            put_blob(uri, data)
            blob_backend(store_root).put("entry.npz", data)

        def unrelated(queue, CACHE, fp):
            queue.put(("run", None))
            CACHE.put(fp, True, None)
        """,
        module="stateright_tpu.store.fixture",
    )
    assert _rules(f) == ["SR002", "SR002"]
    assert f[0].line == 4 and "fenced_savez" in f[0].message
    assert f[1].line == 5


def test_sr002_blob_put_inside_backend_modules_is_sanctioned():
    f = _lint(
        """\
        from .blobstore import put_blob

        def write_record(path, data):
            put_blob(path, data, rotate=True)
        """,
        module="stateright_tpu.faults.ckptio_fixture",
    )
    # Wrong-suffix module still fires; the real blessed suffixes pass.
    assert _rules(f) == ["SR002"]
    f = _lint(
        """\
        def put(self, name, data):
            self._blob.put(name, data)
        """,
        module="stateright_tpu.faults.blobstore",
    )
    assert f == []


def test_sr002_read_open_is_legal_and_ckpt_ok_silences():
    f = _lint(
        """\
        import numpy as np

        def load(path):
            with open(path, "rb") as fh:
                return fh.read()

        def debug_dump(path, arr):
            np.savez(path, arr=arr)  # srlint: ckpt-ok throwaway debug dump, not engine state
        """
    )
    assert f == []


# -- SR003: undeclared detail / REGISTRY keys ----------------------------------


def test_sr003_undeclared_detail_key_fires_declared_passes():
    f = _lint(
        """\
        def build(detail):
            detail["spill_events"] = 3
            detail["totally_new_counter"] = 1
            detail["service"]["queue_wait"] = 0.1
            detail["service"]["made_up"] = 2
        """
    )
    assert _rules(f) == ["SR003", "SR003"]
    assert f[0].line == 3 and "totally_new_counter" in f[0].message
    assert f[1].line == 5 and "service.made_up" in f[1].message


def test_sr003_registry_source_must_be_declared():
    f = _lint(
        """\
        from stateright_tpu.obs import REGISTRY

        def attach(provider):
            REGISTRY.register("frontier", provider)
            REGISTRY.register("mystery_component", provider)
        """
    )
    assert _rules(f) == ["SR003"]
    assert f[0].line == 5 and "mystery_component" in f[0].message


# -- SR004: failure surfaces off the chaos plane -------------------------------


def test_sr004_unguarded_raise_in_engine_scope_fires():
    f = _lint(
        """\
        def transfer(buf):
            if buf is None:
                raise RuntimeError("shard transfer lost its buffer")
        """,
        module="stateright_tpu.store.fixture",
    )
    assert _rules(f) == ["SR004"]
    assert f[0].line == 3 and "maybe_fault()" in f[0].message


def test_sr004_maybe_fault_boundary_or_annotation_passes():
    f = _lint(
        """\
        from stateright_tpu.faults.plan import maybe_fault

        def transfer(buf):
            maybe_fault("store.append")
            if buf is None:
                raise RuntimeError("shard transfer lost its buffer")

        def guard(x):
            if x is None:
                # srlint: fault-ok caller-contract guard, not an I/O surface
                raise RuntimeError("call run() first")
        """,
        module="stateright_tpu.store.fixture",
    )
    assert f == []


def test_sr004_blob_backend_raise_surfaces_are_in_scope():
    # ISSUE 15 satellite: the blob backend's failure surfaces (retry
    # exhaustion -> BlobUnavailable) are engine-adjacent I/O — SR004
    # scope, same as the stores; a maybe_fault boundary in the same
    # function (the blob.* chaos points) is the sanctioned shape.
    f = _lint(
        """\
        class BlobUnavailable(OSError):
            pass

        def op(fn):
            raise BlobUnavailable("blob op exhausted retries")
        """,
        module="stateright_tpu.faults.blobstore_fixture",
    )
    assert _rules(f) == ["SR004"]
    f = _lint(
        """\
        from stateright_tpu.faults.plan import maybe_fault

        class BlobUnavailable(OSError):
            pass

        def op(fn):
            maybe_fault("blob.get")
            raise BlobUnavailable("blob op exhausted retries")
        """,
        module="stateright_tpu.faults.blobstore_fixture",
    )
    assert f == []


def test_sr004_out_of_scope_module_is_exempt():
    f = _lint(
        """\
        def helper(x):
            raise RuntimeError("host-side tooling may raise freely")
        """,
        module="stateright_tpu.utils.fixture",
    )
    assert f == []


# -- SR005: knob literals off the registry -------------------------------------


def test_sr005_typo_comparison_and_restated_universe_fire():
    f = _lint(
        """\
        def build(store, insert_variant="sort"):
            if store == "teired":
                pass
            if insert_variant in ("sort", "phased"):
                pass
        """
    )
    assert _rules(f) == ["SR005", "SR005"]
    assert f[0].line == 2 and "'teired'" in f[0].message
    assert f[1].line == 4 and "restated as a literal" in f[1].message


def test_sr005_registry_members_pass_everywhere():
    f = _lint(
        """\
        from stateright_tpu.knobs import STORE_KINDS

        def build(store="tiered", append=None):
            if store not in STORE_KINDS:
                raise ValueError(store)

        def call():
            build(store="device", append="dus")
        """
    )
    assert f == []


def test_sr005_bad_keyword_and_default_fire():
    f = _lint(
        """\
        def build(table_layout="interleaved"):
            pass

        def call():
            build(table_layout="kv2")
        """
    )
    assert _rules(f) == ["SR005", "SR005"]
    assert f[0].line == 1 and "'interleaved'" in f[0].message
    assert f[1].line == 5 and "'kv2'" in f[1].message


# -- the repo itself is clean --------------------------------------------------


def test_repo_lint_is_clean():
    # The acceptance criterion: every real finding was fixed or carries a
    # reasoned allowlist annotation. A regression here names its own site.
    assert lint_paths(root=ROOT) == []


def test_knob_registry_has_no_drift():
    from stateright_tpu.knobs import check_registry

    assert check_registry() == []


def test_cli_lint_only_exits_zero():
    # The lint half of `python -m stateright_tpu.analysis` (what CI runs on
    # jax-free images); the audit half is covered by the anchor tests below
    # in-process and by scripts/analysis_smoke.py end-to-end.
    from stateright_tpu.analysis.__main__ import main

    assert main(["--skip-audit", "--skip-tools"]) == 0


def test_cli_shell_skip_audit_exits_zero():
    # The CI gate as CI actually invokes it: shell the module entry point
    # itself. This is what keeps every new raise surface (the fleet router
    # and replica drivers included) SR004-gated at TEST time — off-plane
    # failure surfaces fail this test, not a by-hand CLI run three rounds
    # later.
    import subprocess
    import sys

    proc = subprocess.run(
        [
            sys.executable, "-m", "stateright_tpu.analysis",
            "--skip-audit", "--skip-tools",
        ],
        cwd=str(ROOT), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "analysis: clean" in proc.stdout


def test_cli_lint_only_never_imports_jax():
    # The jax-free contract behind --skip-audit: srlint AND the knob-drift
    # pass must run without jax (check_registry skips only the engine
    # cross-check when the import is impossible). A fresh subprocess is the
    # only honest probe — this test file itself imports jax.
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from stateright_tpu.analysis.__main__ import main\n"
        "rc = main(['--skip-audit', '--skip-tools'])\n"
        "assert rc == 0, rc\n"
        "assert 'jax' not in sys.modules, 'lint-only path imported jax'\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=str(ROOT),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- jaxpr auditor: fixtures ---------------------------------------------------


@pytest.fixture(scope="module")
def jnp():
    jax = pytest.importorskip("jax")
    assert len(jax.devices()) >= 8, "conftest pins an 8-device CPU mesh"
    return jax.numpy


def test_full_carry_gather_fixture_is_flagged(jnp):
    # The r8 regression class, distilled: gather most of a table-sized
    # operand in one op. Must be flagged with the op name and a source
    # location in THIS file.
    from stateright_tpu.analysis.auditor import audit_fn

    import jax

    S = 1 << 19  # 2 MiB u32 operand, over the 1 MiB budget
    M = (S * 9) // 10  # moves 90% of it, over the 75% fraction

    def bad_step(table, idx):
        return jnp.take(table, idx, axis=0)  # the full-carry gather

    report = audit_fn(
        bad_step,
        (
            jax.ShapeDtypeStruct((S,), jnp.uint32),
            jax.ShapeDtypeStruct((M,), jnp.int32),
        ),
        name="fixture/full-carry",
        step_mode="total",
    )
    assert not report.clean
    v = next(v for v in report.violations if v.rule == "full-carry-gather")
    assert v.op == "gather"
    assert "test_analysis.py" in v.location  # named site, not "unknown"
    assert "r8 regression" in v.detail


def test_bounded_window_gather_is_legal(jnp):
    # Bucket-row probes gather small windows of big operands — legal.
    from stateright_tpu.analysis.auditor import audit_fn

    import jax

    S = 1 << 19

    def probe(table, idx):
        return jnp.take(table, idx, axis=0)

    report = audit_fn(
        probe,
        (
            jax.ShapeDtypeStruct((S,), jnp.uint32),
            jax.ShapeDtypeStruct((128,), jnp.int32),  # one bucket row
        ),
        name="fixture/probe",
        step_mode="total",
    )
    assert report.clean


def test_callback_inside_step_is_flagged(jnp):
    from stateright_tpu.analysis.auditor import audit_fn

    import jax

    def stepped(x):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    report = audit_fn(
        stepped,
        (jax.ShapeDtypeStruct((64,), jnp.uint32),),
        name="fixture/callback",
        step_mode="total",
    )
    assert [v.rule for v in report.violations] == ["callback"]
    assert report.violations[0].op in ("pure_callback", "callback")


def test_f64_promotion_is_flagged(jnp):
    from stateright_tpu.analysis.auditor import audit_fn

    import jax
    from jax import enable_x64

    def promote(x):
        return x.astype("float64") * 2.0

    with enable_x64():
        report = audit_fn(
            promote,
            (jax.ShapeDtypeStruct((64,), jnp.float32),),
            name="fixture/f64",
            step_mode="total",
        )
    assert any(v.rule == "f64" for v in report.violations)
    assert "promotion" in next(
        v for v in report.violations if v.rule == "f64"
    ).detail


# -- jaxpr auditor: engine anchor budgets --------------------------------------

#: Measured r11 step costs on the 2pc-3 anchors (jax 0.4.37, CPU trace):
#:   frontier  81,037,075 B   299,275,389 flop   8,448 B xfer
#:   resident  84,617,196 B   299,345,395 flop       0 B xfer
#:   sharded  172,554,050 B   633,326,476 flop       0 B xfer
#: Ceilings give ~25% headroom (jaxpr shape drifts slightly across jax
#: versions); the r8 full-carry gather doubled step bytes, so a recurrence
#: clears the ceiling by construction. Floors at roughly half catch a
#: trace that silently stopped measuring the real program.
BUDGETS = {
    "frontier": dict(bytes=(40e6, 101e6), flops=(150e6, 375e6), xfer=8448),
    "resident": dict(bytes=(42e6, 106e6), flops=(150e6, 375e6), xfer=0),
    "sharded": dict(bytes=(85e6, 216e6), flops=(315e6, 790e6), xfer=0),
}


@pytest.fixture(scope="module")
def anchor_results(jnp):
    from stateright_tpu.analysis.anchors import audit_anchors

    return audit_anchors()


@pytest.mark.parametrize("engine", sorted(BUDGETS))
def test_anchor_step_budget(anchor_results, engine):
    ar = anchor_results[engine]
    assert ar.skipped is None, ar.skipped
    b = BUDGETS[engine]
    s = ar.report.summary()
    lo, hi = b["bytes"]
    assert lo <= s["step_hbm_bytes"] <= hi, (
        f"{engine} step bytes {s['step_hbm_bytes']:,} outside "
        f"[{lo:,.0f}, {hi:,.0f}] — a new giant op (or a vanished one); "
        f"run `python -m stateright_tpu.analysis` for the op breakdown"
    )
    flo, fhi = b["flops"]
    assert flo <= s["step_flops"] <= fhi
    # The PCIe floor is shape-derived and exact: the frontier engine
    # re-uploads its popped batch each dispatch, the resident/sharded
    # loops re-upload nothing.
    assert s["transfer_bytes"] == b["xfer"]


@pytest.mark.parametrize("engine", sorted(BUDGETS))
def test_anchor_step_is_violation_free(anchor_results, engine):
    ar = anchor_results[engine]
    assert ar.skipped is None, ar.skipped
    assert ar.report.violations == [], [
        str(v) for v in ar.report.violations
    ]


@pytest.mark.parametrize("engine", sorted(BUDGETS))
def test_anchor_costmodel_cross_check(anchor_results, engine):
    # The jaxpr accounting and tensor/costmodel.py describe the same
    # program: the audited/modeled byte ratio stays inside the pinned band
    # (anchors.MODEL_RATIO_MIN/MAX). A drift means one side changed alone.
    ar = anchor_results[engine]
    assert ar.skipped is None, ar.skipped
    assert ar.ratio_ok, (
        f"{engine} audited/model ratio {ar.ratio:.2f} left the band — "
        "jaxpr and costmodel no longer describe the same program"
    )


def test_anchor_steps_contain_the_insert_chain(anchor_results):
    # Sanity that the trace measured the real engines: every anchor's step
    # contains table gathers AND scatters (the probe/claim chain); an
    # anchor losing them means audit_step() stopped returning the step fn.
    for name, ar in anchor_results.items():
        if ar.skipped:
            continue
        s = ar.report.summary()
        assert s["gathers"] > 0 and s["scatters"] > 0, (name, s)
