"""scanner-check static-analysis suite tests.

Three layers:
  * fixture snippets per pass family — known-bad code must produce the
    expected finding codes, the clean twin must produce none (the
    analyzer's own regression suite);
  * suppression/baseline round-trip — inline pragmas, baseline
    fingerprint stability, mandatory justifications, stale detection;
  * the tier-1 GATE — the analyzer over the whole scanner_tpu package
    must report zero unsuppressed findings (the repo stays lint-clean
    the same way it stays test-green).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from scanner_tpu.analysis.static import (BaselineError, all_passes,
                                         analyze, load_baseline,
                                         run_analysis, split_findings,
                                         write_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _analyze(root, *relfiles):
    return analyze([os.path.join(root, f) for f in relfiles]
                   if relfiles else [str(root)], root=str(root))


def _codes(findings):
    return sorted(f.code for f in findings)


def _write(root, rel, src):
    path = os.path.join(str(root), rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(src))
    return path


# ---------------------------------------------------------------------------
# pass framework basics
# ---------------------------------------------------------------------------

def test_codes_are_unique_and_documented():
    seen = {}
    for p in all_passes():
        assert p.name
        for code, desc in p.codes.items():
            assert code.startswith("SC") and desc
            assert code not in seen, f"{code} claimed by two passes"
            seen[code] = p.name
    assert len(seen) >= 15


def test_syntax_error_is_a_finding(tmp_path):
    _write(tmp_path, "broken.py", "def f(:\n")
    proj, findings = _analyze(tmp_path)
    assert [f.code for f in proj.parse_errors] == ["SC001"]


# ---------------------------------------------------------------------------
# family 1: tracer safety
# ---------------------------------------------------------------------------

TRACER_BAD = """
    import time
    import random
    import numpy as np
    import jax
    import jax.numpy as jnp

    _CACHE = {}

    def poke(v):
        _CACHE["k"] = v

    @jax.jit
    def kern(x):
        if x > 0:                     # SC102
            y = np.sum(x)             # SC101
        else:
            y = jnp.sum(x)
        t = time.time()               # SC103
        r = np.random.rand(3)         # SC103
        s = _CACHE.get("scale", 1.0)  # SC104
        return y * t * s + r.sum()

    _jf = jax.jit(kern)

    def call(frames, k):
        return _jf(frames[:k])        # SC105
"""

TRACER_CLEAN = """
    import functools
    import numpy as np
    import jax
    import jax.numpy as jnp

    TABLE = {"a": 1}   # never mutated from a function: fine to capture

    @functools.partial(jax.jit, static_argnames=("bins",))
    def kern(x, bins):
        if bins > 2:                  # static arg: fine
            return jnp.sum(x)
        if x.ndim == 3:               # shape access: static, fine
            return x.mean()
        h = np.zeros(4)               # numpy on constants: fine
        return x + h[0] + TABLE["a"]

    def host_path(x):
        return np.sum(x)              # not jitted: numpy is fine

    _jf = jax.jit(kern)

    def call(frames):
        return _jf(frames, 4)         # full batch, no ragged slice
"""


def test_tracer_bad_fixture(tmp_path):
    _write(tmp_path, "bad.py", TRACER_BAD)
    _, findings = _analyze(tmp_path)
    counts = {c: _codes(findings).count(c) for c in set(_codes(findings))}
    assert counts.get("SC101") == 1
    assert counts.get("SC102") == 1
    assert counts.get("SC103") == 2
    assert counts.get("SC104") == 1
    assert counts.get("SC105") == 1


def test_tracer_clean_fixture(tmp_path):
    _write(tmp_path, "clean.py", TRACER_CLEAN)
    _, findings = _analyze(tmp_path)
    assert not [f for f in findings if f.code.startswith("SC1")], \
        [f.format() for f in findings]


# SC106 scope is engine/kernels code: the same snippet is bad inside an
# engine/ directory and invisible outside it (host tooling may pin chips)
AFFINITY_BAD = """
    import jax
    from jax import local_devices

    def stage(x):
        d = jax.devices()[0]              # SC106: fixed-chip pin
        return jax.device_put(x), d       # SC106: bare device_put

    def probe():
        return local_devices()[0]         # SC106: aliased import pin
"""

AFFINITY_CLEAN = """
    import jax

    def stage(x, device):
        # explicit (possibly-None) device: placement decided upstream
        return jax.device_put(x, device)

    def probe():
        return jax.default_backend() == "tpu"   # platform probe, no pin

    def enumerate_chips():
        return list(jax.local_devices())        # whole list: no pin
"""


def test_affinity_bad_fixture_in_engine_scope(tmp_path):
    _write(tmp_path, "engine/bad_dev.py", AFFINITY_BAD)
    _, findings = _analyze(tmp_path)
    assert _codes(findings).count("SC106") == 3, \
        [f.format() for f in findings]


def test_affinity_clean_fixture_and_scope(tmp_path):
    _write(tmp_path, "kernels/clean_dev.py", AFFINITY_CLEAN)
    # identical bad code OUTSIDE engine/kernels scope: not SC106's beat
    _write(tmp_path, "tools/pinner.py", AFFINITY_BAD)
    _, findings = _analyze(tmp_path)
    assert "SC106" not in _codes(findings), \
        [f.format() for f in findings]


def test_tracer_scan_body_and_kernel_execute(tmp_path):
    _write(tmp_path, "scanny.py", """
        import time
        import jax

        def body(carry, x):
            t = time.time()           # SC103: scan body is traced
            return carry + x * t, x

        def drive(xs, ev):
            import jax.numpy as jnp
            out = jax.lax.scan(body, jnp.zeros(()), xs)
            return out, ev.kernel.execute(xs)   # SC105: raw execute()
    """)
    _, findings = _analyze(tmp_path)
    assert "SC103" in _codes(findings)
    assert "SC105" in _codes(findings)


# ---------------------------------------------------------------------------
# family 2: concurrency
# ---------------------------------------------------------------------------

CONC_BAD = """
    import threading
    import time

    class Svc:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()
            self.n = 0

        def ab(self):
            with self.a:
                with self.b:          # SC201 (vs ba)
                    self.n = 1

        def ba(self):
            with self.b:
                with self.a:
                    return self.n

        def reenter(self):
            with self.a:
                self.ab()             # SC201 self-deadlock

        def slow(self):
            with self.a:
                time.sleep(0.5)       # SC202

        def bare(self):
            self.n = 2                # SC203
"""

CONC_CLEAN = """
    import threading
    import queue

    class Svc:
        def __init__(self):
            self.a = threading.RLock()
            self.b = threading.Lock()
            self.n = 0
            self.q = queue.Queue()

        def ab(self):
            with self.a:
                with self.b:
                    self.n = 1

        def ab2(self):
            with self.a:              # same order: fine
                with self.b:
                    return self.n

        def reenter(self):
            with self.a:
                self.ab()             # RLock: reentry is fine

        def bounded(self):
            with self.b:
                return self.q.get(timeout=0.25)   # bounded: fine

        def read_only(self):
            return self.n             # read, not write: fine
"""


def test_concurrency_bad_fixture(tmp_path):
    _write(tmp_path, "svc.py", CONC_BAD)
    _, findings = _analyze(tmp_path)
    codes = _codes(findings)
    assert codes.count("SC201") == 2   # ABBA + self-deadlock
    assert "SC202" in codes
    assert "SC203" in codes


def test_concurrency_clean_fixture(tmp_path):
    _write(tmp_path, "svc.py", CONC_CLEAN)
    _, findings = _analyze(tmp_path)
    assert not [f for f in findings if f.code.startswith("SC2")], \
        [f.format() for f in findings]


# ---------------------------------------------------------------------------
# family 3: contracts (synthetic mini-repo)
# ---------------------------------------------------------------------------

def _contract_repo(tmp_path):
    _write(tmp_path, "setup.py", "# root marker\n")
    _write(tmp_path, "docs/observability.md", """
        | `scanner_tpu_good_total` | counter | documented |
        | `scanner_tpu_ghost_total` | counter | documented but unregistered |
    """)
    _write(tmp_path, "docs/guide.md", """
        `SCANNER_TPU_DOCUMENTED` is a knob.  `[net] port` is config.
        The key `port` is documented here.
    """)
    _write(tmp_path, "pkg/config.py", """
        def default_config():
            return {"net": {"port": 1}}
    """)
    _write(tmp_path, "pkg/util/faults.py", """
        SITES = ("rpc.call", "storage.write")
        MODES = ("raise", "delay")
        _EXC = {"fault": lambda m: Exception(m)}
        NAMED_PLANS = {"p1": "rpc.call:raise", "p2": "nosuch.site:crash",
                       "p3": "rpc.call:explode:n=1",
                       "p4": "rpc.call:raise:exc=nosuchexc"}
        ACTIVE = False

        def inject(site, data=None, detail=""):
            return data
    """)
    _write(tmp_path, "pkg/m.py", """
        import os
        from .util import faults as _faults

        def registry():
            return None

        M_GOOD = registry().counter("scanner_tpu_good_total", "ok")
        M_UNDOC = registry().counter("scanner_tpu_undoc_total", "x")
        M_BAD = registry().counter("BadName", "x")
        M_NOTOT = registry().counter("scanner_tpu_rows", "x")
        M_NOHELP = registry().gauge("scanner_tpu_depth", "")

        def knobs(cfg):
            a = os.environ.get("SCANNER_TPU_DOCUMENTED")
            b = os.environ.get("SCANNER_TPU_SECRET")
            return a, b, cfg["net"]["port"], cfg["net"]["missing"]

        def hooks(data):
            data = _faults.inject("rpc.call", data)
            return _faults.inject("typo.site", data)

        class RpcServer:
            def __init__(self, name, methods, port=0):
                pass

        def serve(handler):
            return RpcServer("svc", {"Reg": handler})

        def client(c):
            return c.call("NotRegistered")
    """)
    return tmp_path


def test_contract_fixture_codes(tmp_path):
    _contract_repo(tmp_path)
    _, findings = _analyze(tmp_path, "pkg")
    by_code = {}
    for f in findings:
        by_code.setdefault(f.code, []).append(f)

    msgs = [f.message for f in by_code.get("SC301", [])]
    assert any("scanner_tpu_undoc_total" in m for m in msgs)
    assert any("scanner_tpu_ghost_total" in m for m in msgs)
    # name pattern + counter-_total + empty help
    assert len(by_code.get("SC302", [])) == 3
    msgs = [f.message for f in by_code.get("SC303", [])]
    assert any("SCANNER_TPU_SECRET" in m for m in msgs)
    assert not any("SCANNER_TPU_DOCUMENTED" in m for m in msgs)
    msgs = [f.message for f in by_code.get("SC304", [])]
    assert any("missing" in m for m in msgs)
    assert not any("[net] port" in m for m in msgs)
    msgs = [f.message for f in by_code.get("SC305", [])]
    assert any("typo.site" in m for m in msgs)          # unknown inject
    assert any("storage.write" in m for m in msgs)      # unwired site
    assert any("nosuch.site" in m for m in msgs)        # bad named plan
    assert any("unknown mode `explode`" in m for m in msgs)
    assert any("unknown exc `nosuchexc`" in m for m in msgs)
    # the valid clause shapes raise nothing extra
    assert not any("`raise`" in m for m in msgs)
    msgs = [f.message for f in by_code.get("SC306", [])]
    assert any("NotRegistered" in m for m in msgs)      # called, no server
    assert any("`Reg`" in m for m in msgs)              # registered, dead
    assert by_code.get("SC307"), "missing RPC_CONTRACTS must be flagged"


def _alert_repo(tmp_path, doc_rules=("rule_a", "rule_b"),
                code_rules=("rule_a", "rule_b"),
                cfg_keys=("enabled", "rules"),
                schema_keys=("enabled", "rules"),
                with_markers=True):
    """Synthetic mini-repo for the SC308 alert-rule contract lints."""
    _write(tmp_path, "setup.py", "# root marker\n")
    rows = "\n".join(f"| `{n}` | warning | something |"
                     for n in doc_rules)
    table = (f"<!-- default-alert-rules:begin -->\n"
             f"| Rule | Severity | Fires when |\n|---|---|---|\n"
             f"{rows}\n<!-- default-alert-rules:end -->\n"
             if with_markers else rows)
    _write(tmp_path, "docs/observability.md", f"""
        Default ruleset table:

        {table}

        The keys `enabled`, `rules` and `bogus` are documented so the
        SC304 lint stays quiet in this fixture.
    """)
    rules = ",\n            ".join(
        f'Rule(name="{n}", series="scanner_tpu_x")' for n in code_rules)
    schema = ", ".join(f'"{k}"' for k in schema_keys)
    _write(tmp_path, "pkg/util/health.py", f"""
        def Rule(**kw):
            return kw

        CONFIG_KEYS = ({schema},)

        DEFAULT_RULES = (
            {rules},
        )
    """)
    cfg = ", ".join(f'"{k}": 1' for k in cfg_keys)
    _write(tmp_path, "pkg/config.py", f"""
        def default_config():
            return {{"alerts": {{{cfg}}}}}
    """)
    return tmp_path


def test_alert_contract_clean_fixture_is_quiet(tmp_path):
    _alert_repo(tmp_path)
    _, findings = _analyze(tmp_path, "pkg")
    assert [f for f in findings if f.code == "SC308"] == []


def test_alert_contract_rule_names_both_directions(tmp_path):
    _alert_repo(tmp_path, doc_rules=("rule_a", "rule_ghost"),
                code_rules=("rule_a", "rule_undoc"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC308"]
    assert any("rule_undoc" in m and "missing from" in m for m in msgs)
    assert any("rule_ghost" in m and "no such rule" in m for m in msgs)
    assert not any("`rule_a`" in m for m in msgs)


def test_alert_contract_missing_marker_table(tmp_path):
    _alert_repo(tmp_path, with_markers=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC308"]
    assert any("marker table" in m for m in msgs)


def test_alert_contract_config_schema_both_directions(tmp_path):
    _alert_repo(tmp_path, cfg_keys=("enabled", "rules", "bogus"),
                schema_keys=("enabled", "rules", "interval"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC308"]
    assert any("[alerts] bogus" in m and "does not accept" in m
               for m in msgs)
    assert any("`interval`" in m and "declares no" in m for m in msgs)
    assert not any("enabled" in m for m in msgs)


def _cost_repo(tmp_path, kernel_has_cost=True,
               declared=("scanner_tpu_eff_a", "scanner_tpu_eff_b"),
               registered=("scanner_tpu_eff_a", "scanner_tpu_eff_b"),
               doc_series=("scanner_tpu_eff_a", "scanner_tpu_eff_b"),
               with_markers=True):
    """Synthetic mini-repo for the SC309 cost-model contract lints."""
    _write(tmp_path, "setup.py", "# root marker\n")
    cost = ("\n            def cost(self, shapes):\n"
            "                return None\n" if kernel_has_cost else "\n")
    _write(tmp_path, "pkg/kernels/imgk.py", f"""
        from pkg.common import DeviceType
        from pkg.graph.ops import Kernel, register_op

        @register_op(device=DeviceType.TPU, batch=4)
        class DeviceK(Kernel):
            def execute(self, frame):
                return frame
{cost}
        @register_op()
        class HostK(Kernel):
            def execute(self, frame):
                return frame
    """)
    regs = "\n        ".join(
        f'_G{i} = _mx.registry().gauge("{n}", "help text", '
        f'labels=["op"])' for i, n in enumerate(registered))
    decl = ", ".join(f'"{n}"' for n in declared)
    _write(tmp_path, "pkg/util/coststats.py", f"""
        from . import metrics as _mx

        {regs}

        EFFICIENCY_SERIES = ({decl},)
    """)
    rows = "\n".join(f"| `{n}` | gauge | x |" for n in doc_series)
    table = (f"<!-- efficiency-series:begin -->\n"
             f"| Series | Type | Meaning |\n|---|---|---|\n"
             f"{rows}\n<!-- efficiency-series:end -->\n"
             if with_markers else rows)
    all_series = sorted(set(declared) | set(registered) | set(doc_series))
    _write(tmp_path, "docs/observability.md", f"""
        Catalog (every fixture series mentioned so SC301 stays quiet):
        {" ".join(f"`{n}`" for n in all_series)}

        {table}
    """)
    return tmp_path


def test_cost_model_kernel_hook_fixture(tmp_path):
    _cost_repo(tmp_path, kernel_has_cost=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC309"]
    assert any("DeviceK" in m and "cost()" in m for m in msgs)
    # host kernels (no device=TPU) are exempt
    assert not any("HostK" in m for m in msgs)


def test_cost_model_clean_fixture_is_quiet(tmp_path):
    _cost_repo(tmp_path)
    _, findings = _analyze(tmp_path, "pkg")
    assert [f for f in findings if f.code == "SC309"] == []


def test_cost_model_series_all_pairings_both_directions(tmp_path):
    _cost_repo(
        tmp_path,
        declared=("scanner_tpu_eff_a", "scanner_tpu_eff_phantom"),
        registered=("scanner_tpu_eff_a", "scanner_tpu_eff_unlisted"),
        doc_series=("scanner_tpu_eff_a", "scanner_tpu_eff_ghost"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC309"]
    # registered but not declared
    assert any("scanner_tpu_eff_unlisted" in m
               and "missing from EFFICIENCY_SERIES" in m for m in msgs)
    # declared but never registered
    assert any("scanner_tpu_eff_phantom" in m
               and "registers no such series" in m for m in msgs)
    # declared but missing from the doc table
    assert any("scanner_tpu_eff_phantom" in m and "missing from the"
               in m for m in msgs)
    # doc table lists an unknown series
    assert any("scanner_tpu_eff_ghost" in m and "no such series" in m
               for m in msgs)
    assert not any("`scanner_tpu_eff_a`" in m for m in msgs)


def test_cost_model_missing_marker_table(tmp_path):
    _cost_repo(tmp_path, with_markers=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC309"]
    assert any("marker" in m for m in msgs)


def _framecache_repo(tmp_path,
                     declared=("scanner_tpu_framecache_a",
                               "scanner_tpu_framecache_b"),
                     registered=("scanner_tpu_framecache_a",
                                 "scanner_tpu_framecache_b"),
                     doc_series=("scanner_tpu_framecache_a",
                                 "scanner_tpu_framecache_b"),
                     cfg_keys=("frame_cache_enabled", "frame_cache_mb"),
                     schema_keys=("frame_cache_enabled",
                                  "frame_cache_mb"),
                     with_markers=True):
    """Synthetic mini-repo for the SC310 frame-cache contract lints."""
    _write(tmp_path, "setup.py", "# root marker\n")
    regs = "\n        ".join(
        f'_G{i} = _mx.registry().counter("{n}", "help text", '
        f'labels=["device"])' for i, n in enumerate(registered))
    decl = ", ".join(f'"{n}"' for n in declared)
    schema = ", ".join(f'"{k}"' for k in schema_keys)
    _write(tmp_path, "pkg/engine/framecache.py", f"""
        from ..util import metrics as _mx

        {regs}

        FRAMECACHE_SERIES = ({decl},)

        CONFIG_KEYS = ({schema},)
    """)
    _write(tmp_path, "pkg/util/metrics.py", """
        def registry():
            return None
    """)
    cfg = ", ".join(f'"{k}": 1' for k in cfg_keys)
    _write(tmp_path, "pkg/config.py", f"""
        def default_config():
            return {{"perf": {{{cfg}}}}}
    """)
    rows = "\n".join(f"| `{n}` | counter | x |" for n in doc_series)
    table = (f"<!-- framecache-series:begin -->\n"
             f"| Series | Type | Meaning |\n|---|---|---|\n"
             f"{rows}\n<!-- framecache-series:end -->\n"
             if with_markers else rows)
    all_series = sorted(set(declared) | set(registered) | set(doc_series))
    keys = " ".join(f"`{k}`"
                    for k in sorted(set(cfg_keys) | set(schema_keys)))
    _write(tmp_path, "docs/observability.md", f"""
        Catalog (every fixture series mentioned so SC301 stays quiet):
        {" ".join(f"`{n}`" for n in all_series)}

        Config keys documented for SC304: {keys}

        {table}
    """)
    return tmp_path


def test_framecache_clean_fixture_is_quiet(tmp_path):
    _framecache_repo(tmp_path)
    _, findings = _analyze(tmp_path, "pkg")
    assert [f for f in findings if f.code == "SC310"] == []


def test_framecache_series_all_pairings_both_directions(tmp_path):
    _framecache_repo(
        tmp_path,
        declared=("scanner_tpu_framecache_a",
                  "scanner_tpu_framecache_phantom"),
        registered=("scanner_tpu_framecache_a",
                    "scanner_tpu_framecache_unlisted"),
        doc_series=("scanner_tpu_framecache_a",
                    "scanner_tpu_framecache_ghost"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC310"]
    assert any("scanner_tpu_framecache_unlisted" in m
               and "missing from FRAMECACHE_SERIES" in m for m in msgs)
    assert any("scanner_tpu_framecache_phantom" in m
               and "registers no such series" in m for m in msgs)
    assert any("scanner_tpu_framecache_phantom" in m
               and "missing from the" in m for m in msgs)
    assert any("scanner_tpu_framecache_ghost" in m
               and "no such series" in m for m in msgs)
    assert not any("`scanner_tpu_framecache_a`" in m for m in msgs)


def test_framecache_missing_marker_table(tmp_path):
    _framecache_repo(tmp_path, with_markers=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC310"]
    assert any("marker table" in m for m in msgs)


def test_framecache_config_schema_both_directions(tmp_path):
    _framecache_repo(
        tmp_path,
        cfg_keys=("frame_cache_enabled", "frame_cache_mb",
                  "frame_cache_bogus"),
        schema_keys=("frame_cache_enabled", "frame_cache_mb",
                     "frame_cache_pages"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC310"]
    assert any("[perf] frame_cache_bogus" in m
               and "does not accept" in m for m in msgs)
    assert any("`frame_cache_pages`" in m and "declares no" in m
               for m in msgs)
    assert not any("frame_cache_enabled" in m for m in msgs)


def _fusion_repo(tmp_path,
                 declared=("scanner_tpu_fusion_a",
                           "scanner_tpu_fusion_b"),
                 registered=("scanner_tpu_fusion_a",
                             "scanner_tpu_fusion_b"),
                 doc_series=("scanner_tpu_fusion_a",
                             "scanner_tpu_fusion_b"),
                 cfg_keys=("fusion_enabled", "fusion_second_key"),
                 schema_keys=("fusion_enabled", "fusion_second_key"),
                 with_markers=True,
                 kernel_has_cost=True):
    """Synthetic mini-repo for the SC317 fusion contract lints."""
    _write(tmp_path, "setup.py", "# root marker\n")
    regs = "\n        ".join(
        f'_G{i} = _mx.registry().counter("{n}", "help text", '
        f'labels=["chain"])' for i, n in enumerate(registered))
    decl = ", ".join(f'"{n}"' for n in declared)
    schema = ", ".join(f'"{k}"' for k in schema_keys)
    _write(tmp_path, "pkg/graph/fusion.py", f"""
        from ..util import metrics as _mx

        {regs}

        FUSION_SERIES = ({decl},)

        CONFIG_KEYS = ({schema},)
    """)
    _write(tmp_path, "pkg/util/metrics.py", """
        def registry():
            return None
    """)
    cost = ("\n            def cost(self, shapes):\n"
            "                return None\n" if kernel_has_cost else "")
    _write(tmp_path, "pkg/kernels/k.py", f"""
        class FzKernel:
            def execute(self, frame):
                return frame

            def execute_traced(self, frame):
                return frame
        {cost}
    """)
    cfg = ", ".join(f'"{k}": 1' for k in cfg_keys)
    _write(tmp_path, "pkg/config.py", f"""
        def default_config():
            return {{"perf": {{{cfg}}}}}
    """)
    rows = "\n".join(f"| `{n}` | counter | x |" for n in doc_series)
    table = (f"<!-- fusion-series:begin -->\n"
             f"| Series | Type | Meaning |\n|---|---|---|\n"
             f"{rows}\n<!-- fusion-series:end -->\n"
             if with_markers else rows)
    all_series = sorted(set(declared) | set(registered) | set(doc_series))
    keys = " ".join(f"`{k}`"
                    for k in sorted(set(cfg_keys) | set(schema_keys)))
    _write(tmp_path, "docs/observability.md", f"""
        Catalog (every fixture series mentioned so SC301 stays quiet):
        {" ".join(f"`{n}`" for n in all_series)}

        Config keys documented for SC304: {keys}

        {table}
    """)
    return tmp_path


def test_fusion_clean_fixture_is_quiet(tmp_path):
    _fusion_repo(tmp_path)
    _, findings = _analyze(tmp_path, "pkg")
    assert [f for f in findings if f.code == "SC317"] == []


def test_fusion_series_all_pairings_both_directions(tmp_path):
    _fusion_repo(
        tmp_path,
        declared=("scanner_tpu_fusion_a", "scanner_tpu_fusion_phantom"),
        registered=("scanner_tpu_fusion_a",
                    "scanner_tpu_fusion_unlisted"),
        doc_series=("scanner_tpu_fusion_a", "scanner_tpu_fusion_ghost"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC317"]
    assert any("scanner_tpu_fusion_unlisted" in m
               and "missing from FUSION_SERIES" in m for m in msgs)
    assert any("scanner_tpu_fusion_phantom" in m
               and "registers no such series" in m for m in msgs)
    assert any("scanner_tpu_fusion_phantom" in m
               and "missing from" in m and "fusion-series" in m
               for m in msgs)
    assert any("scanner_tpu_fusion_ghost" in m
               and "no such series" in m for m in msgs)
    assert not any("`scanner_tpu_fusion_a`" in m for m in msgs)


def test_fusion_missing_marker_table(tmp_path):
    _fusion_repo(tmp_path, with_markers=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC317"]
    assert any("marker table" in m for m in msgs)


def test_fusion_config_schema_both_directions(tmp_path):
    _fusion_repo(
        tmp_path,
        cfg_keys=("fusion_enabled", "fusion_second_key", "fusion_bogus"),
        schema_keys=("fusion_enabled", "fusion_second_key",
                     "fusion_ghost_knob"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC317"]
    assert any("[perf] fusion_bogus" in m and "does not accept" in m
               for m in msgs)
    assert any("`fusion_ghost_knob`" in m and "declares no" in m
               for m in msgs)
    assert not any("fusion_enabled" in m for m in msgs)


def test_fusion_execute_traced_without_cost(tmp_path):
    """extends SC309: a kernel advertising the fusion trace hook
    (execute_traced) without a cost() descriptor silently never fuses
    — the planner's fusability gate keys on cost()."""
    _fusion_repo(tmp_path, kernel_has_cost=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC317"]
    assert any("FzKernel" in m and "cost()" in m for m in msgs)


def _remediation_repo(tmp_path,
                      code_pbs=(("pb_a", "rule_a"), ("pb_b", "rule_b")),
                      rule_names=("rule_a", "rule_b"),
                      doc_rows=(("pb_a", "rule_a"), ("pb_b", "rule_b")),
                      cfg_keys=("enabled", "dry_run"),
                      schema_keys=("enabled", "dry_run"),
                      with_markers=True):
    """Synthetic mini-repo for the SC311 remediation contract lints."""
    _write(tmp_path, "setup.py", "# root marker\n")
    pbs = ",\n            ".join(
        f'Playbook(name="{n}", alert="{a}", action="act_{n}")'
        for n, a in code_pbs)
    schema = ", ".join(f'"{k}"' for k in schema_keys)
    _write(tmp_path, "pkg/engine/controller.py", f"""
        def Playbook(**kw):
            return kw

        CONFIG_KEYS = ({schema},)

        DEFAULT_PLAYBOOKS = (
            {pbs},
        )
    """)
    rules = ",\n            ".join(
        f'Rule(name="{n}", series="scanner_tpu_x")' for n in rule_names)
    _write(tmp_path, "pkg/util/health.py", f"""
        def Rule(**kw):
            return kw

        DEFAULT_RULES = (
            {rules},
        )
    """)
    cfg = ", ".join(f'"{k}": 1' for k in cfg_keys)
    _write(tmp_path, "pkg/config.py", f"""
        def default_config():
            return {{"remediation": {{{cfg}}}}}
    """)
    rows = "\n".join(f"| `{n}` | `{a}` | act | 5 s | env |"
                     for n, a in doc_rows)
    table = (f"<!-- remediation-playbooks:begin -->\n"
             f"| Playbook | Alert | Action | Cooldown | Kill switch |\n"
             f"|---|---|---|---|---|\n"
             f"{rows}\n<!-- remediation-playbooks:end -->\n"
             if with_markers else rows)
    keys = " ".join(f"`{k}`"
                    for k in sorted(set(cfg_keys) | set(schema_keys)))
    _write(tmp_path, "docs/robustness.md", f"""
        Remediation playbook matrix:

        {table}
    """)
    _write(tmp_path, "docs/observability.md", f"""
        Config keys documented for SC304: {keys}
    """)
    return tmp_path


def test_remediation_clean_fixture_is_quiet(tmp_path):
    _remediation_repo(tmp_path)
    _, findings = _analyze(tmp_path, "pkg")
    assert [f for f in findings if f.code == "SC311"] == []


def test_remediation_unknown_alert_binding(tmp_path):
    _remediation_repo(tmp_path,
                      code_pbs=(("pb_a", "rule_a"),
                                ("pb_b", "rule_ghost")),
                      doc_rows=(("pb_a", "rule_a"),
                                ("pb_b", "rule_ghost")))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC311"]
    assert any("`pb_b`" in m and "rule_ghost" in m
               and "no such rule" in m for m in msgs)
    assert not any("`pb_a`" in m for m in msgs)


def test_remediation_docs_matrix_both_directions(tmp_path):
    _remediation_repo(tmp_path,
                      code_pbs=(("pb_a", "rule_a"),
                                ("pb_undoc", "rule_b")),
                      doc_rows=(("pb_a", "rule_b"),
                                ("pb_ghost", "rule_a")))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC311"]
    # code playbook absent from docs
    assert any("`pb_undoc`" in m and "missing from" in m for m in msgs)
    # docs row with no code playbook
    assert any("`pb_ghost`" in m and "no such playbook" in m
               for m in msgs)
    # alert binding mismatch between code and the docs row
    assert any("`pb_a`" in m and "docs matrix row says" in m
               for m in msgs)


def test_remediation_missing_marker_table(tmp_path):
    _remediation_repo(tmp_path, with_markers=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC311"]
    assert any("marker table" in m for m in msgs)


def test_remediation_config_schema_both_directions(tmp_path):
    _remediation_repo(tmp_path,
                      cfg_keys=("enabled", "dry_run", "bogus"),
                      schema_keys=("enabled", "dry_run", "min_only"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC311"]
    assert any("[remediation] bogus" in m and "does not accept" in m
               for m in msgs)
    assert any("`min_only`" in m and "declares no" in m for m in msgs)
    assert not any("enabled" in m for m in msgs)


def _fence_repo(tmp_path, wrap_mut=True, wrap_read=False,
                schema_keys=("journal_enabled",
                             "journal_rotate_records"),
                cfg_keys=("journal_enabled", "journal_rotate_records")):
    """Synthetic mini-repo for the SC312 generation-fence lints."""
    _write(tmp_path, "setup.py", "# root marker\n")
    mut = "self._fenced(self._rpc_mut)" if wrap_mut else "self._rpc_mut"
    read = "self._fenced(self._rpc_read)" if wrap_read \
        else "self._rpc_read"
    _write(tmp_path, "pkg/svc.py", f"""
        MASTER_SERVICE = "svc.Master"
        WORKER_SERVICE = "svc.Worker"

        RPC_CONTRACTS = {{
            "Mut": {{"timeout_s": 1.0, "idempotent": False}},
            "Read": {{"timeout_s": 1.0, "idempotent": True}},
        }}

        class RpcServer:
            def __init__(self, name, methods, port=0):
                pass

        class Master:
            def __init__(self):
                self._server = RpcServer(MASTER_SERVICE, {{
                    "Mut": {mut},
                    "Read": {read},
                }})

            def _fenced(self, fn):
                return fn

            def _rpc_mut(self, req):
                return {{}}

            def _rpc_read(self, req):
                return {{}}

        class Worker:
            def __init__(self):
                # worker-service registrations are outside SC312's
                # scope even when unwrapped
                self._server = RpcServer(WORKER_SERVICE, {{
                    "Read": lambda req: {{}},
                }})

        def client(c):
            c.call("Mut")
            c.call("Read")
    """)
    schema = ", ".join(f'"{k}"' for k in schema_keys)
    _write(tmp_path, "pkg/engine/journal.py",
           f"CONFIG_KEYS = ({schema},)\n")
    cfg = ", ".join(f'"{k}": 1' for k in cfg_keys)
    _write(tmp_path, "pkg/config.py", f"""
        def default_config():
            return {{"robustness": {{{cfg}}}}}
    """)
    _write(tmp_path, "docs/guide.md", """
        The keys `journal_enabled`, `journal_rotate_records`,
        `journal_extra` and `journal_ghost` are documented so SC304
        stays quiet in this fixture.
    """)
    return tmp_path


def test_fence_clean_fixture_is_quiet(tmp_path):
    _fence_repo(tmp_path)
    _, findings = _analyze(tmp_path, "pkg")
    assert [f for f in findings if f.code == "SC312"] == []


def test_fence_unwrapped_mutating_handler_flagged(tmp_path):
    _fence_repo(tmp_path, wrap_mut=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC312"]
    assert any("`Mut`" in m and "without the generation-fence" in m
               for m in msgs)
    assert not any("`Read`" in m for m in msgs)


def test_fence_wrapped_idempotent_handler_flagged(tmp_path):
    _fence_repo(tmp_path, wrap_read=True)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC312"]
    assert any("`Read`" in m and "idempotent=False" in m for m in msgs)
    assert not any("`Mut`" in m for m in msgs)


def test_fence_journal_config_keys_both_directions(tmp_path):
    _fence_repo(tmp_path,
                schema_keys=("journal_enabled", "journal_ghost"),
                cfg_keys=("journal_enabled", "journal_extra"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC312"]
    assert any("journal_extra" in m and "does not accept" in m
               for m in msgs)
    assert any("journal_ghost" in m and "declares no" in m
               for m in msgs)
    assert not any("journal_enabled" in m for m in msgs)


def _gang_repo(tmp_path, wrap_gang=True, idem_gang=False,
               schema_keys=("enabled", "init_timeout_s"),
               cfg_keys=("enabled", "init_timeout_s"),
               doc_keys=("enabled", "init_timeout_s")):
    """Synthetic mini-repo for the SC313 gang-contract lints."""
    _write(tmp_path, "setup.py", "# root marker\n")
    gm = "self._fenced(self._rpc_gang)" if wrap_gang \
        else "self._rpc_gang"
    idem = "True" if idem_gang else "False"
    _write(tmp_path, "pkg/svc.py", f"""
        MASTER_SERVICE = "svc.Master"

        RPC_CONTRACTS = {{
            "GangFailed": {{"timeout_s": 1.0, "idempotent": {idem}}},
            "Read": {{"timeout_s": 1.0, "idempotent": True}},
        }}

        class RpcServer:
            def __init__(self, name, methods, port=0):
                pass

        class Master:
            def __init__(self):
                self._server = RpcServer(MASTER_SERVICE, {{
                    "GangFailed": {gm},
                    "Read": self._rpc_read,
                }})

            def _fenced(self, fn):
                return fn

            def _rpc_gang(self, req):
                return {{}}

            def _rpc_read(self, req):
                return {{}}

        def client(c):
            c.call("GangFailed")
            c.call("Read")
    """)
    schema = ", ".join(f'"{k}"' for k in schema_keys)
    _write(tmp_path, "pkg/engine/gang.py",
           f"CONFIG_KEYS = ({schema},)\n")
    cfg = ", ".join(f'"{k}": 1' for k in cfg_keys)
    _write(tmp_path, "pkg/config.py", f"""
        def default_config():
            return {{"gang": {{{cfg}}}}}
    """)
    rows = "\n".join(f"| `[gang] {k}` | a row |" for k in doc_keys)
    _write(tmp_path, "docs/guide.md", f"""
        The keys `enabled`, `init_timeout_s`, `ghost_key` and
        `extra_key` are mentioned so SC304 stays quiet.

        {rows}
    """)
    return tmp_path


def test_gang_clean_fixture_is_quiet(tmp_path):
    _gang_repo(tmp_path)
    _, findings = _analyze(tmp_path, "pkg")
    assert [f for f in findings if f.code == "SC313"] == []


def test_gang_unfenced_handler_flagged(tmp_path):
    _gang_repo(tmp_path, wrap_gang=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC313"]
    assert any("`GangFailed`" in m and "generation-fence" in m
               for m in msgs)


def test_gang_misclassified_idempotent_flagged(tmp_path):
    """SC312 cannot see a Gang entry misclassified idempotent=True
    (it only inspects idempotent=False entries) — SC313 pins the gang
    surface from the other side."""
    _gang_repo(tmp_path, idem_gang=True)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC313"]
    assert any("`GangFailed`" in m and "idempotent=False" in m
               for m in msgs)
    assert not any("`Read`" in m for m in msgs)


def test_gang_config_keys_all_pairings(tmp_path):
    _gang_repo(tmp_path,
               schema_keys=("enabled", "ghost_key"),
               cfg_keys=("enabled", "extra_key"),
               doc_keys=("enabled",))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC313"]
    # config declares a key the module refuses
    assert any("extra_key" in m and "does not accept" in m
               for m in msgs)
    # module accepts a key config never declares
    assert any("ghost_key" in m and "declares no" in m for m in msgs)
    # module accepts a key guide.md has no row for
    assert any("ghost_key" in m and "guide.md" in m for m in msgs)
    assert not any("`enabled`" in m for m in msgs)


def test_gang_doc_row_without_schema_key_flagged(tmp_path):
    _gang_repo(tmp_path, doc_keys=("enabled", "init_timeout_s",
                                   "phantom_row"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC313"]
    assert any("phantom_row" in m and "no such key" in m for m in msgs)


def _clocksync_repo(tmp_path,
                    cs_declared=("scanner_tpu_clock_offset_seconds",
                                 "scanner_tpu_clock_uncert_seconds"),
                    cs_registered=("scanner_tpu_clock_offset_seconds",
                                   "scanner_tpu_clock_uncert_seconds"),
                    gp_declared=("scanner_tpu_gang_phase_seconds",),
                    gp_registered=("scanner_tpu_gang_phase_seconds",),
                    doc_series=None,
                    spans=("gang.rendezvous", "gang.barrier"),
                    doc_spans=None,
                    cfg_keys=("enabled", "clocksync_enabled",
                              "rebase_clocks"),
                    schema_keys=("clocksync_enabled", "rebase_clocks"),
                    with_series_markers=True,
                    with_span_markers=True):
    """Synthetic mini-repo for the SC314 cross-host time lints.

    gang.py always also registers a lifecycle counter that is NOT in
    GANG_PHASE_SERIES — the reverse leg must only claim
    phase/skew-named series, not every gang metric."""
    if doc_series is None:
        doc_series = tuple(cs_declared) + tuple(gp_declared)
    if doc_spans is None:
        doc_spans = spans
    _write(tmp_path, "setup.py", "# root marker\n")
    regs = "\n        ".join(
        f'_G{i} = _mx.registry().gauge("{n}", "help text", '
        f'labels=["node"])' for i, n in enumerate(cs_registered))
    decl = ", ".join(f'"{n}"' for n in cs_declared)
    schema = ", ".join(f'"{k}"' for k in schema_keys)
    _write(tmp_path, "pkg/util/clocksync.py", f"""
        from . import metrics as _mx

        {regs}

        CLOCKSYNC_SERIES = ({decl},)

        CONFIG_KEYS = ({schema},)
    """)
    gregs = "\n        ".join(
        f'_P{i} = _mx.registry().counter("{n}", "help text", '
        f'labels=["phase"])' for i, n in enumerate(gp_registered))
    gdecl = ", ".join(f'"{n}"' for n in gp_declared)
    opens = "\n            ".join(
        f'_tr.open_span(None, "{s}")' for s in spans)
    _write(tmp_path, "pkg/engine/gang.py", f"""
        from ..util import metrics as _mx
        from ..util import tracing as _tr

        _M_FORMED = _mx.registry().counter(
            "scanner_tpu_gang_formed_total", "help text")

        {gregs}

        GANG_PHASE_SERIES = ({gdecl},)

        def member():
            {opens}
    """)
    _write(tmp_path, "pkg/util/metrics.py", """
        def registry():
            return None
    """)
    _write(tmp_path, "pkg/util/tracing.py", """
        def open_span(tracer, name, **kw):
            return None
    """)
    cfg = ", ".join(f'"{k}": True' for k in cfg_keys)
    _write(tmp_path, "pkg/config.py", f"""
        def default_config():
            return {{"trace": {{{cfg}}}}}
    """)
    rows = "\n".join(f"| `{n}` | gauge | x |" for n in doc_series)
    stable = (f"<!-- clocksync-series:begin -->\n"
              f"| Series | Type | Meaning |\n|---|---|---|\n"
              f"{rows}\n<!-- clocksync-series:end -->\n"
              if with_series_markers else rows)
    srows = "\n".join(f"| `{s}` | a phase |" for s in doc_spans)
    ptable = (f"<!-- gang-phase-taxonomy:begin -->\n"
              f"| Span | Meaning |\n|---|---|\n"
              f"{srows}\n<!-- gang-phase-taxonomy:end -->\n"
              if with_span_markers else srows)
    all_series = sorted(set(cs_declared) | set(cs_registered)
                        | set(gp_declared) | set(gp_registered)
                        | set(doc_series)
                        | {"scanner_tpu_gang_formed_total"})
    keys = " ".join(f"`{k}`"
                    for k in sorted(set(cfg_keys) | set(schema_keys)))
    _write(tmp_path, "docs/observability.md", f"""
        Catalog (every fixture series mentioned so SC301 stays quiet):
        {" ".join(f"`{n}`" for n in all_series)}

        Config keys documented for SC304: {keys}

        {stable}

        {ptable}
    """)
    return tmp_path


def test_clocksync_clean_fixture_is_quiet(tmp_path):
    _clocksync_repo(tmp_path)
    _, findings = _analyze(tmp_path, "pkg")
    assert [f for f in findings if f.code == "SC314"] == []


def test_clocksync_series_all_pairings_both_directions(tmp_path):
    _clocksync_repo(
        tmp_path,
        cs_declared=("scanner_tpu_clock_offset_seconds",
                     "scanner_tpu_clock_phantom"),
        cs_registered=("scanner_tpu_clock_offset_seconds",
                       "scanner_tpu_clock_unlisted"),
        doc_series=("scanner_tpu_clock_offset_seconds",
                    "scanner_tpu_gang_phase_seconds",
                    "scanner_tpu_clock_ghost"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC314"]
    assert any("scanner_tpu_clock_unlisted" in m
               and "missing from CLOCKSYNC_SERIES" in m for m in msgs)
    assert any("scanner_tpu_clock_phantom" in m
               and "registers no such series" in m for m in msgs)
    assert any("scanner_tpu_clock_phantom" in m
               and "missing from the" in m for m in msgs)
    assert any("scanner_tpu_clock_ghost" in m
               and "has such a series" in m for m in msgs)
    assert not any("`scanner_tpu_clock_offset_seconds`" in m
                   for m in msgs)


def test_clocksync_gang_phase_series_scoped_to_phase_names(tmp_path):
    """The reverse leg on gang.py must flag an undeclared
    phase/skew-named registration but NOT the lifecycle counters the
    module also owns (SC310's exact-pairing shape would false-positive
    on every gang metric)."""
    _clocksync_repo(
        tmp_path,
        gp_declared=("scanner_tpu_gang_phase_seconds",),
        gp_registered=("scanner_tpu_gang_phase_seconds",
                       "scanner_tpu_gang_barrier_skew_seconds"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC314"]
    assert any("scanner_tpu_gang_barrier_skew_seconds" in m
               and "missing from GANG_PHASE_SERIES" in m for m in msgs)
    assert not any("scanner_tpu_gang_formed_total" in m for m in msgs)


def test_clocksync_missing_marker_tables(tmp_path):
    _clocksync_repo(tmp_path, with_series_markers=False,
                    with_span_markers=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC314"]
    assert any("clocksync-series" in m and "marker table" in m
               for m in msgs)
    assert any("gang-phase-taxonomy" in m and "marker table" in m
               for m in msgs)


def test_clocksync_span_taxonomy_both_directions(tmp_path):
    _clocksync_repo(
        tmp_path,
        spans=("gang.rendezvous", "gang.barrier", "gang.stealth"),
        doc_spans=("gang.rendezvous", "gang.barrier", "gang.phantom"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC314"]
    assert any("`gang.stealth`" in m and "no row" in m for m in msgs)
    assert any("`gang.phantom`" in m and "opens no" in m for m in msgs)
    assert not any("`gang.barrier`" in m for m in msgs)


def test_clocksync_trace_config_keys_both_directions(tmp_path):
    """`[trace] enabled` belongs to the tracing core and is exempt;
    every other [trace] key must pair with clocksync.CONFIG_KEYS."""
    _clocksync_repo(
        tmp_path,
        cfg_keys=("enabled", "clocksync_enabled", "bogus_key"),
        schema_keys=("clocksync_enabled", "ghost_key"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC314"]
    assert any("bogus_key" in m and "does not accept" in m
               for m in msgs)
    assert any("ghost_key" in m and "declares no" in m for m in msgs)
    assert not any("`enabled`" in m for m in msgs)
    assert not any("clocksync_enabled" in m for m in msgs)


_SHARD_FIX_SERIES = ("scanner_tpu_gang_shard_rows_total",
                     "scanner_tpu_gang_shard_commit_folds_total")


def _gang_shard_repo(tmp_path,
                     declared=_SHARD_FIX_SERIES,
                     registered=None,
                     doc_series=None,
                     schema_keys=("enabled", "sharded",
                                  "halo_exchange"),
                     cfg_keys=("enabled", "sharded", "halo_exchange"),
                     with_markers=True,
                     with_tuple=True):
    """Synthetic mini-repo for the SC315 sharded-gang data-plane
    lints.  gang.py also registers a lifecycle counter NOT named
    `_shard_` — the reverse leg must only claim shard-named series."""
    if registered is None:
        registered = declared
    if doc_series is None:
        doc_series = declared
    _write(tmp_path, "setup.py", "# root marker\n")
    regs = "\n        ".join(
        f'_S{i} = _mx.registry().counter("{n}", "help text", '
        f'labels=["role"])' for i, n in enumerate(registered))
    decl = (f"GANG_SHARD_SERIES = ("
            + ", ".join(f'"{n}"' for n in declared) + ",)"
            if with_tuple else "")
    schema = ", ".join(f'"{k}"' for k in schema_keys)
    _write(tmp_path, "pkg/engine/gang.py", f"""
        from ..util import metrics as _mx

        _M_FORMED = _mx.registry().counter(
            "scanner_tpu_gang_formed_total", "help text")

        {regs}

        {decl}

        CONFIG_KEYS = ({schema},)
    """)
    _write(tmp_path, "pkg/util/metrics.py", """
        def registry():
            return None
    """)
    cfg = ", ".join(f'"{k}": True' for k in cfg_keys)
    _write(tmp_path, "pkg/config.py", f"""
        def default_config():
            return {{"gang": {{{cfg}}}}}
    """)
    rows = "\n".join(f"| `{n}` | counter | `role` | x |"
                     for n in doc_series)
    stable = (f"<!-- gang-shard-series:begin -->\n"
              f"| Series | Type | Labels | Meaning |\n|---|---|---|"
              f"---|\n{rows}\n<!-- gang-shard-series:end -->\n"
              if with_markers else rows)
    all_series = sorted(set(declared) | set(registered)
                        | set(doc_series)
                        | {"scanner_tpu_gang_formed_total"})
    _write(tmp_path, "docs/observability.md", f"""
        Catalog (every fixture series mentioned so SC301 stays
        quiet): {" ".join(f"`{n}`" for n in all_series)}

        {stable}
    """)
    gkeys = "\n".join(f"| `[gang] {k}` | a row |"
                      for k in sorted(set(schema_keys)
                                      | set(cfg_keys)))
    _write(tmp_path, "docs/guide.md", f"""
        Keys mentioned so SC304 stays quiet: `enabled` `sharded`
        `halo_exchange`

        {gkeys}
    """)
    return tmp_path


def test_gang_shard_clean_fixture_is_quiet(tmp_path):
    _gang_shard_repo(tmp_path)
    _, findings = _analyze(tmp_path, "pkg")
    assert [f for f in findings if f.code == "SC315"] == []


def test_gang_shard_series_all_pairings_both_directions(tmp_path):
    _gang_shard_repo(
        tmp_path,
        declared=("scanner_tpu_gang_shard_rows_total",
                  "scanner_tpu_gang_shard_phantom_total"),
        registered=("scanner_tpu_gang_shard_rows_total",
                    "scanner_tpu_gang_shard_unlisted_total"),
        doc_series=("scanner_tpu_gang_shard_rows_total",
                    "scanner_tpu_gang_shard_ghost_total"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC315"]
    assert any("scanner_tpu_gang_shard_unlisted_total" in m
               and "missing from GANG_SHARD_SERIES" in m for m in msgs)
    assert any("scanner_tpu_gang_shard_phantom_total" in m
               and "registers no such series" in m for m in msgs)
    assert any("scanner_tpu_gang_shard_phantom_total" in m
               and "missing from the" in m for m in msgs)
    assert any("scanner_tpu_gang_shard_ghost_total" in m
               and "no such series" in m for m in msgs)
    assert not any("`scanner_tpu_gang_shard_rows_total`" in m
                   for m in msgs)
    # the lifecycle counter the module also owns is NOT claimed
    assert not any("scanner_tpu_gang_formed_total" in m for m in msgs)


def test_gang_shard_missing_marker_table(tmp_path):
    _gang_shard_repo(tmp_path, with_markers=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC315"]
    assert any("gang-shard-series" in m and "marker table" in m
               for m in msgs)


def test_gang_shard_missing_tuple_flagged(tmp_path):
    _gang_shard_repo(tmp_path, with_tuple=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC315"]
    assert any("declares no GANG_SHARD_SERIES tuple" in m
               for m in msgs)


def test_gang_shard_gate_keys_travel_with_plane(tmp_path):
    """The data plane without its `[gang]` gates — both the schema
    side (kill switch) and the config side (declared default)."""
    _gang_shard_repo(tmp_path,
                     schema_keys=("enabled", "sharded"),
                     cfg_keys=("enabled", "halo_exchange"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC315"]
    assert any("halo_exchange" in m and "kill switch" in m
               for m in msgs)
    assert any("sharded" in m and "declared default" in m
               for m in msgs)


def test_gang_shard_gate_without_plane_flagged(tmp_path):
    """CONFIG_KEYS carrying the sharding gates while the module has
    no shard data plane at all — stale gate surface."""
    _gang_shard_repo(tmp_path, registered=(), with_tuple=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC315"]
    assert any("sharded" in m and "nothing to gate" in m
               for m in msgs)
    assert any("halo_exchange" in m and "nothing to gate" in m
               for m in msgs)


_SHARDMAP_FIX_SERIES = ("scanner_tpu_shard_map_epoch",
                        "scanner_tpu_shard_failovers_total")


def _shardmap_repo(tmp_path,
                   declared=_SHARDMAP_FIX_SERIES,
                   registered=None,
                   doc_series=None,
                   schema_keys=("shards",),
                   cfg_keys=("shards",),
                   routed=("Mut",),
                   wrap_mut=True,
                   with_markers=True,
                   with_tuple=True):
    """Synthetic mini-repo for the SC316 sharded control-plane
    lints: a shardmap module with its series catalog + [control]
    schema, and a master service whose SHARD_ROUTED_RPCS tuple must
    agree with the idempotent=False, fence-wrapped surface."""
    if registered is None:
        registered = declared
    if doc_series is None:
        doc_series = declared
    _write(tmp_path, "setup.py", "# root marker\n")
    regs = "\n        ".join(
        f'_S{i} = _mx.registry().counter("{n}", "help text", '
        f'labels=["role"])' for i, n in enumerate(registered))
    decl = (f"SHARD_SERIES = ("
            + ", ".join(f'"{n}"' for n in declared) + ",)"
            if with_tuple else "")
    schema = ", ".join(f'"{k}"' for k in schema_keys)
    _write(tmp_path, "pkg/engine/shardmap.py", f"""
        from ..util import metrics as _mx

        {regs}

        {decl}

        CONFIG_KEYS = ({schema},)
    """)
    _write(tmp_path, "pkg/util/metrics.py", """
        def registry():
            return None
    """)
    mut = "self._fenced(self._rpc_mut)" if wrap_mut \
        else "self._rpc_mut"
    routed_decl = "SHARD_ROUTED_RPCS = (" \
        + "".join(f'"{r}", ' for r in routed) + ")"
    _write(tmp_path, "pkg/engine/service.py", f"""
        MASTER_SERVICE = "svc.Master"

        RPC_CONTRACTS = {{
            "Mut": {{"timeout_s": 1.0, "idempotent": False}},
            "Read": {{"timeout_s": 1.0, "idempotent": True}},
        }}

        {routed_decl}

        class RpcServer:
            def __init__(self, name, methods, port=0):
                pass

        class Master:
            def __init__(self):
                self._server = RpcServer(MASTER_SERVICE, {{
                    "Mut": {mut},
                    "Read": self._rpc_read,
                }})

            def _fenced(self, fn):
                return fn

            def _rpc_mut(self, req):
                return {{}}

            def _rpc_read(self, req):
                return {{}}

        def client(c):
            c.call("Mut")
            c.call("Read")
    """)
    cfg = ", ".join(f'"{k}": 1' for k in cfg_keys)
    _write(tmp_path, "pkg/config.py", f"""
        def default_config():
            return {{"control": {{{cfg}}}}}
    """)
    rows = "\n".join(f"| `{n}` | counter | `role` | x |"
                     for n in doc_series)
    stable = (f"<!-- shard-series:begin -->\n"
              f"| Series | Type | Labels | Meaning |\n|---|---|---|"
              f"---|\n{rows}\n<!-- shard-series:end -->\n"
              if with_markers else rows)
    all_series = sorted(set(declared) | set(registered)
                        | set(doc_series))
    _write(tmp_path, "docs/observability.md", f"""
        Catalog (every fixture series mentioned so SC301 stays
        quiet): {" ".join(f"`{n}`" for n in all_series)}

        {stable}
    """)
    ckeys = " ".join(f"`{k}`" for k in sorted(set(schema_keys)
                                              | set(cfg_keys)))
    _write(tmp_path, "docs/guide.md", f"""
        Keys mentioned so SC304 stays quiet: {ckeys}
    """)
    return tmp_path


def test_shardmap_clean_fixture_is_quiet(tmp_path):
    _shardmap_repo(tmp_path)
    _, findings = _analyze(tmp_path, "pkg")
    assert [f for f in findings if f.code == "SC316"] == []


def test_shardmap_series_all_pairings_both_directions(tmp_path):
    _shardmap_repo(
        tmp_path,
        declared=("scanner_tpu_shard_map_epoch",
                  "scanner_tpu_shard_phantom_total"),
        registered=("scanner_tpu_shard_map_epoch",
                    "scanner_tpu_shard_unlisted_total"),
        doc_series=("scanner_tpu_shard_map_epoch",
                    "scanner_tpu_shard_ghost_total"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC316"]
    assert any("scanner_tpu_shard_unlisted_total" in m
               and "missing from SHARD_SERIES" in m for m in msgs)
    assert any("scanner_tpu_shard_phantom_total" in m
               and "registers no such series" in m for m in msgs)
    assert any("scanner_tpu_shard_phantom_total" in m
               and "missing from the" in m for m in msgs)
    assert any("scanner_tpu_shard_ghost_total" in m
               and "no such series" in m for m in msgs)
    assert not any("`scanner_tpu_shard_map_epoch`" in m
                   for m in msgs)


def test_shardmap_missing_marker_table(tmp_path):
    _shardmap_repo(tmp_path, with_markers=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC316"]
    assert any("shard-series" in m and "marker table" in m
               for m in msgs)


def test_shardmap_missing_tuple_flagged(tmp_path):
    _shardmap_repo(tmp_path, with_tuple=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC316"]
    assert any("declares no SHARD_SERIES tuple" in m for m in msgs)


def test_shardmap_control_config_keys_both_directions(tmp_path):
    _shardmap_repo(tmp_path,
                   schema_keys=("shards", "schema_only"),
                   cfg_keys=("shards", "cfg_only"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC316"]
    assert any("[control] cfg_only" in m and "does not accept" in m
               for m in msgs)
    assert any("`schema_only`" in m and "declares no" in m
               for m in msgs)
    assert not any("`shards`" in m for m in msgs)


def test_shardmap_routed_rpc_must_be_mutating(tmp_path):
    """Routing an idempotent read through bulk-ownership dispatch is
    flagged — only mutating RPCs follow the bulk to its shard."""
    _shardmap_repo(tmp_path, routed=("Mut", "Read"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC316"]
    assert any("`Read`" in m and "idempotent=False" in m
               for m in msgs)
    assert not any("`Mut`" in m for m in msgs)


def test_shardmap_mutating_rpc_must_be_routed(tmp_path):
    """An idempotent=False contract missing from SHARD_ROUTED_RPCS
    would pin mutations to the dial-time shard."""
    _shardmap_repo(tmp_path, routed=())
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC316"]
    assert any("`Mut`" in m and "missing from SHARD_ROUTED_RPCS" in m
               for m in msgs)
    assert not any("`Read`" in m for m in msgs)


def test_shardmap_routed_rpc_must_stay_fenced(tmp_path):
    """A shard-routed handler outside the generation fence reopens
    the stale-master window (the SC312 extension leg)."""
    _shardmap_repo(tmp_path, wrap_mut=False)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC316"]
    assert any("`Mut`" in m and "without the generation-fence" in m
               for m in msgs)


def test_shardmap_routed_phantom_method_flagged(tmp_path):
    _shardmap_repo(tmp_path, routed=("Mut", "Ghost"))
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC316"]
    assert any("`Ghost`" in m and "no such entry" in m for m in msgs)


def test_contract_rpc_contracts_table_both_directions(tmp_path):
    _write(tmp_path, "setup.py", "# root\n")
    _write(tmp_path, "pkg/rpcmod.py", """
        RPC_CONTRACTS = {
            "Reg": {"timeout_s": 1.0, "idempotent": True},
            "Phantom": {"timeout_s": 1.0, "idempotent": True},
        }

        class RpcServer:
            def __init__(self, name, methods, port=0):
                pass

        def serve(h):
            return RpcServer("svc", {"Reg": h, "Unclassified": h})

        def client(c):
            c.call("Reg")
            c.call("Unclassified")
    """)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC307"]
    assert any("Unclassified" in m for m in msgs)
    assert any("Phantom" in m for m in msgs)
    assert not any("`Reg`" in m for m in msgs)


def test_contract_rpc_contracts_entry_completeness(tmp_path):
    """SC307 also rejects present-but-incomplete entries: every
    classification needs BOTH `timeout_s` and `idempotent`, as dict
    literals the lint can see."""
    _write(tmp_path, "setup.py", "# root\n")
    _write(tmp_path, "pkg/rpcmod.py", """
        TIMEOUTS = {"timeout_s": 1.0}

        RPC_CONTRACTS = {
            "Full": {"timeout_s": 1.0, "idempotent": True},
            "NoIdem": {"timeout_s": 1.0},
            "NoTimeout": {"idempotent": True},
            "NotADict": TIMEOUTS,
        }

        class RpcServer:
            def __init__(self, name, methods, port=0):
                pass

        def serve(h):
            return RpcServer("svc", {"Full": h, "NoIdem": h,
                                     "NoTimeout": h, "NotADict": h})

        def client(c):
            c.call("Full")
            c.call("NoIdem")
            c.call("NoTimeout")
            c.call("NotADict")
    """)
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC307"]
    assert any("NoIdem" in m and "idempotent" in m for m in msgs)
    assert any("NoTimeout" in m and "timeout_s" in m for m in msgs)
    assert any("NotADict" in m and "dict literal" in m for m in msgs)
    assert not any("`Full`" in m for m in msgs)


# ---------------------------------------------------------------------------
# suppression + baseline round-trip
# ---------------------------------------------------------------------------

SLEEPY = """
    import threading
    import time

    class S:
        def __init__(self):
            self.l = threading.Lock()

        def slow(self):
            with self.l:
                time.sleep(1)
"""


def test_inline_suppression(tmp_path):
    _write(tmp_path, "s.py", SLEEPY.replace(
        "time.sleep(1)",
        "time.sleep(1)  # scanner-check: disable=SC202 test shim"))
    proj, findings = _analyze(tmp_path)
    res = split_findings(proj, findings)
    assert not res.unsuppressed
    assert [f.code for f in res.inline_suppressed] == ["SC202"]


def test_file_level_suppression(tmp_path):
    _write(tmp_path, "s.py",
           "# scanner-check: disable-file=SC202\n" + textwrap.dedent(
               SLEEPY))
    proj, findings = _analyze(tmp_path)
    res = split_findings(proj, findings)
    assert not res.unsuppressed and res.inline_suppressed


def test_baseline_round_trip(tmp_path):
    _write(tmp_path, "s.py", SLEEPY)
    proj, findings = _analyze(tmp_path)
    res = split_findings(proj, findings)
    assert [f.code for f in res.unsuppressed] == ["SC202"]

    bl_path = str(tmp_path / "baseline.json")
    new = write_baseline(bl_path, res.unsuppressed)
    assert new == 1
    # placeholder justification must be rejected
    with pytest.raises(BaselineError):
        load_baseline(bl_path)
    doc = json.load(open(bl_path))
    doc["entries"][0]["justification"] = "intentional for the test"
    json.dump(doc, open(bl_path, "w"))
    baseline = load_baseline(bl_path)

    # baselined finding no longer reported...
    res2 = split_findings(proj, findings, baseline)
    assert not res2.unsuppressed
    assert [f.code for f in res2.baselined] == ["SC202"]

    # ...and the fingerprint survives the code MOVING (line shift)
    _write(tmp_path, "s.py", "# a new leading comment\n\n"
           + textwrap.dedent(SLEEPY))
    proj3, findings3 = _analyze(tmp_path)
    res3 = split_findings(proj3, findings3, baseline)
    assert not res3.unsuppressed and res3.baselined

    # fixing the code makes the entry STALE (prunable), not silent
    _write(tmp_path, "s.py", textwrap.dedent(SLEEPY).replace(
        "time.sleep(1)", "pass"))
    proj4, findings4 = _analyze(tmp_path)
    res4 = split_findings(proj4, findings4, baseline)
    assert not res4.unsuppressed
    assert len(res4.stale_baseline) == 1

    # re-writing keeps existing justifications
    _write(tmp_path, "s.py", SLEEPY)
    proj5, findings5 = _analyze(tmp_path)
    res5 = split_findings(proj5, findings5)
    assert write_baseline(bl_path, res5.unsuppressed,
                          previous=baseline) == 0
    assert load_baseline(bl_path)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_json_and_exit_codes(tmp_path):
    _write(tmp_path, "setup.py", "# root\n")
    bad = _write(tmp_path, "pkg/s.py", SLEEPY)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scanner_check.py"),
         bad, "--root", str(tmp_path), "--json"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["counts"] == {"SC202": 1}
    assert doc["findings"][0]["path"] == "pkg/s.py"

    clean = _write(tmp_path, "pkg/ok.py", "x = 1\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scanner_check.py"),
         clean, "--root", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr

    # --write-baseline under --select must refuse: a selected run can't
    # see other codes' findings, so a rewrite would erase their entries
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scanner_check.py"),
         bad, "--root", str(tmp_path), "--select", "SC3",
         "--write-baseline"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 2 and "erase" in r.stderr, \
        r.stdout + r.stderr


# ---------------------------------------------------------------------------
# the tier-1 gate
# ---------------------------------------------------------------------------

def test_repo_is_clean():
    """THE gate: scanner-check over the whole package reports zero
    unsuppressed findings.  A new finding means: fix it, or suppress it
    inline / baseline it WITH a one-line justification (reviewed like
    code).  load_baseline() already rejects justification-less entries,
    so a clean pass here also certifies the baseline's hygiene."""
    baseline_path = os.path.join(REPO, "tools",
                                 "scanner_check_baseline.json")
    baseline = load_baseline(baseline_path)   # raises on TODO entries
    pkg = os.path.join(REPO, "scanner_tpu")
    proj, findings = analyze([pkg], root=REPO)
    res = split_findings(proj, findings, baseline)
    assert not res.unsuppressed, \
        "scanner-check found new issues:\n" + "\n".join(
            f.format() for f in res.unsuppressed)
    assert not res.stale_baseline, \
        ("baseline entries no longer match any finding — prune them "
         f"(tools/scanner_check.py --write-baseline): "
         f"{res.stale_baseline}")


def test_run_analysis_select():
    findings = run_analysis([os.path.join(REPO, "scanner_tpu")],
                            root=REPO, select=["SC2"])
    assert all(f.code.startswith("SC2") for f in findings)


# ---------------------------------------------------------------------------
# family 4: durability & fencing (SC401-SC406)
# ---------------------------------------------------------------------------

def _sc4(findings):
    return sorted(f.code for f in findings if f.code.startswith("SC4"))


DUR_WRITE_AHEAD_BAD = """
    import threading

    MASTER_SERVICE = "scanner-master"
    RECORD_TYPES = ("done",)

    class RpcServer:
        def __init__(self, name, methods, port=0):
            pass

    class Master:
        def __init__(self):
            self._fence = threading.Event()
            self.done = set()

        def _fenced(self, h):
            return h

        def _journal_append(self, recs):
            if self._fence.is_set():
                return

        def _apply(self, rec):
            t = rec.get("t")
            if t == "done":
                self.done.add(rec["task"])

        def _rpc_finish(self, req):
            recs = []
            recs.append({"t": "done", "task": req["task"]})
            self.done.add(req["task"])
            if req.get("fast"):
                return {"ok": True}
            self._journal_append(recs)
            return {"ok": True}

        def serve(self):
            return RpcServer(MASTER_SERVICE, {
                "FinishedWork": self._fenced(self._rpc_finish),
            })
"""

DUR_WRITE_AHEAD_CLEAN = DUR_WRITE_AHEAD_BAD.replace(
    """\
        def _rpc_finish(self, req):
            recs = []
            recs.append({"t": "done", "task": req["task"]})
            self.done.add(req["task"])
            if req.get("fast"):
                return {"ok": True}
            self._journal_append(recs)
            return {"ok": True}
""",
    """\
        def _rpc_finish(self, req):
            recs = []
            try:
                recs.append({"t": "done", "task": req["task"]})
                self.done.add(req["task"])
                if req.get("fast"):
                    return {"ok": True, "fast": True}
                return {"ok": True}
            finally:
                self._journal_append(recs)
""")


def test_write_ahead_dirty_ack_flagged(tmp_path):
    _write(tmp_path, "m.py", DUR_WRITE_AHEAD_BAD)
    _, findings = _analyze(tmp_path)
    sc401 = [f for f in findings if f.code == "SC401"]
    assert len(sc401) == 1
    assert "_rpc_finish" in sc401[0].message
    assert "FinishedWork" in sc401[0].message


def test_write_ahead_finally_commit_is_clean(tmp_path):
    """The journal-in-finally idiom: every return flows through the
    enclosing finally's group-commit first, so no path acks dirty."""
    _write(tmp_path, "m.py", DUR_WRITE_AHEAD_CLEAN)
    _, findings = _analyze(tmp_path)
    assert _sc4(findings) == []


def test_write_ahead_inline_suppression(tmp_path):
    _write(tmp_path, "m.py", DUR_WRITE_AHEAD_BAD.replace(
        "return {\"ok\": True}\n            self._journal_append",
        "return {\"ok\": True}  "
        "# scanner-check: disable=SC401 volatile-only fast path\n"
        "            self._journal_append"))
    proj, findings = _analyze(tmp_path)
    res = split_findings(proj, findings)
    assert not [f for f in res.unsuppressed if f.code == "SC401"]
    assert [f.code for f in res.inline_suppressed] == ["SC401"]


DUR_FENCE_BAD = """
    import threading

    MASTER_SERVICE = "scanner-master"
    RECORD_TYPES = ("strike",)

    class RpcServer:
        def __init__(self, name, methods, port=0):
            pass

    class Master:
        def __init__(self):
            self.transient_failures = {}

        def _journal_append(self, recs):
            pass

        def _apply(self, rec):
            t = rec.get("t")
            if t == "strike":
                self.transient_failures.pop(rec["w"], None)

        def _rpc_unreg(self, req):
            recs = self._requeue(req["worker"])
            self._journal_append(recs)
            return {"ok": True}

        def _requeue(self, wid):
            self.transient_failures.update({wid: 1})
            return [{"t": "strike", "w": wid}]

        def serve(self):
            return RpcServer(MASTER_SERVICE, {
                "UnregisterWorker": self._rpc_unreg,
            })
"""

# the real fix's idiom: the unfenced handler consults the fence before
# reaching the durable mutation, so it participates in the protocol
DUR_FENCE_CLEAN = DUR_FENCE_BAD.replace(
    """\
        def _rpc_unreg(self, req):
            recs = self._requeue(req["worker"])
""",
    """\
        def _rpc_unreg(self, req):
            if self._fence.is_set():
                return {"ok": True}
            recs = self._requeue(req["worker"])
""")


def test_fence_unfenced_handler_mutation_flagged(tmp_path):
    _write(tmp_path, "m.py", DUR_FENCE_BAD)
    _, findings = _analyze(tmp_path)
    sc402 = [f for f in findings if f.code == "SC402"]
    assert len(sc402) == 1
    assert "_requeue" in sc402[0].message
    assert "UnregisterWorker" in sc402[0].message


def test_fence_consulting_handler_is_clean(tmp_path):
    _write(tmp_path, "m.py", DUR_FENCE_CLEAN)
    _, findings = _analyze(tmp_path)
    assert _sc4(findings) == []


def test_fence_background_thread_target_flagged(tmp_path):
    """Thread(target=self.X) is an entry point the fence audit follows,
    same as an unfenced handler."""
    _write(tmp_path, "m.py", DUR_FENCE_BAD.replace(
        """\
        def serve(self):
""",
        """\
        def start(self):
            threading.Thread(target=self._scan, daemon=True).start()

        def _scan(self):
            self._requeue(0)

        def serve(self):
"""))
    _, findings = _analyze(tmp_path)
    msgs = [f.message for f in findings if f.code == "SC402"]
    assert any("background thread `_scan`" in m for m in msgs)


DUR_STALE_BAD = """
    class ShardState:
        def __init__(self):
            self.committed_jobs = set()
            self.map_epoch = 0

        def apply_equality(self, msg):
            e = msg.get("map_epoch")
            if e == self.map_epoch:
                self.committed_jobs.add(msg["job"])
            return True

        def apply_blind(self, msg):
            self.map_epoch = msg["map_epoch"]
            self.committed_jobs.add(msg["job"])
"""

DUR_STALE_CLEAN = """
    class ShardState:
        def __init__(self):
            self.committed_jobs = set()
            self.map_epoch = 0

        def apply_monotone(self, msg):
            e = msg.get("map_epoch")
            if e <= self.map_epoch:
                return False
            self.map_epoch = e
            self.committed_jobs.add(msg["job"])
            return True

        def apply_cas(self, msg):
            if not try_claim(msg["epoch"]):
                return False
            self.committed_jobs.add(msg["job"])
            return True

        def apply_delegated(self, msg):
            self._validate(msg)
            self.committed_jobs.add(msg["job"])

        def apply_latch(self, msg):
            self.map_epoch = max(self.map_epoch, msg["map_epoch"])

        def _validate(self, msg):
            return True
"""


def test_staleness_equality_check_flagged(tmp_path):
    _write(tmp_path, "m.py", DUR_STALE_BAD)
    _, findings = _analyze(tmp_path)
    msgs = [f.message for f in findings if f.code == "SC403"]
    assert len(msgs) == 2
    assert any("apply_equality" in m and "equality" in m for m in msgs)
    assert any("apply_blind" in m and "without any" in m for m in msgs)


def test_staleness_monotone_cas_delegation_clean(tmp_path):
    """Monotone compares, CAS claims, max()-latches, and passing the
    stamped message to a validator all count as discipline."""
    _write(tmp_path, "m.py", DUR_STALE_CLEAN)
    _, findings = _analyze(tmp_path)
    assert _sc4(findings) == []


def test_staleness_non_mutating_reader_exempt(tmp_path):
    """A pure reader may compare epochs however it likes (the gang
    liveness probe uses exact-epoch equality legitimately)."""
    _write(tmp_path, "m.py", """
        def peek(self, msg, live):
            return msg.get("epoch") == live
    """)
    _, findings = _analyze(tmp_path)
    assert _sc4(findings) == []


DUR_JOURNAL_BAD = """
    RECORD_TYPES = ("done", "strike")

    def _journal_append(recs):
        pass

    def writer(recs):
        recs.append({"t": "done"})
        recs.append({"t": "orphan"})

    def replay(rec):
        t = rec.get("t")
        if t == "done":
            return 1
        if t == "ghost":
            return 2
        return 0
"""

DUR_JOURNAL_CLEAN = """
    RECORD_TYPES = ("done", "strike")

    def _journal_append(recs):
        pass

    def writer(recs):
        recs.append({"t": "done"})
        recs.append({"t": "strike"})

    def replay(rec):
        t = rec.get("t")
        if t in ("done", "strike"):
            return 1
        return 0
"""


def test_journal_round_trip_all_directions(tmp_path):
    _write(tmp_path, "j.py", DUR_JOURNAL_BAD)
    _, findings = _analyze(tmp_path)
    msgs = [f.message for f in findings if f.code == "SC404"]
    assert any("`orphan`" in m and "no" in m and "replay" in m
               for m in msgs)
    assert any("`orphan`" in m and "RECORD_TYPES" in m for m in msgs)
    assert any("`ghost`" in m and "nothing" in m for m in msgs)
    assert any("`strike`" in m and "declares" in m for m in msgs)


def test_journal_round_trip_clean(tmp_path):
    """Membership (`t in (...)`) arms count as replay coverage."""
    _write(tmp_path, "j.py", DUR_JOURNAL_CLEAN)
    _, findings = _analyze(tmp_path)
    assert _sc4(findings) == []


DUR_LOCK_BAD = """
    import threading

    MASTER_SERVICE = "scanner-master"
    RECORD_TYPES = ("done",)

    class RpcServer:
        def __init__(self, name, methods, port=0):
            pass

    class Master:
        def __init__(self):
            self._lock = threading.Lock()
            self._fence = threading.Event()

        def _journal_append(self, recs):
            if self._fence.is_set():
                return

        def _apply(self, rec):
            if rec.get("t") == "done":
                return 1

        def _rpc_get(self, req):
            return {}

        def flush_locked(self):
            with self._lock:
                self._journal_append([{"t": "done"}])

        def wait_locked(self):
            with self._lock:
                self._collective_digest_sum()

        def indirect(self):
            with self._lock:
                self._maybe_commit()

        def _maybe_commit(self):
            self._journal_append([])

        def serve(self):
            return RpcServer(MASTER_SERVICE, {
                "GetJob": self._rpc_get,
            })
"""

DUR_LOCK_CLEAN = DUR_LOCK_BAD.replace(
    """\
        def flush_locked(self):
            with self._lock:
                self._journal_append([{"t": "done"}])

        def wait_locked(self):
            with self._lock:
                self._collective_digest_sum()

        def indirect(self):
            with self._lock:
                self._maybe_commit()
""",
    """\
        def flush_locked(self):
            recs = [{"t": "done"}]
            with self._lock:
                staged = list(recs)
            self._journal_append(staged)

        def wait_locked(self):
            self._collective_digest_sum()

        def indirect(self):
            with self._lock:
                pass
            self._maybe_commit()
""")


def test_lock_across_commit_flagged(tmp_path):
    _write(tmp_path, "m.py", DUR_LOCK_BAD)
    _, findings = _analyze(tmp_path)
    msgs = [f.message for f in findings if f.code == "SC405"]
    assert len(msgs) == 3
    assert any("group-commit while holding" in m for m in msgs)
    assert any("collective wait" in m for m in msgs)
    assert any("_maybe_commit" in m and "transitively" in m
               for m in msgs)


def test_lock_released_before_commit_clean(tmp_path):
    _write(tmp_path, "m.py", DUR_LOCK_CLEAN)
    _, findings = _analyze(tmp_path)
    assert _sc4(findings) == []


def _sc406_repo(tmp_path, anchors, transitions, contracts=True):
    _write(tmp_path, "setup.py", "# root\n")
    if contracts:
        _write(tmp_path, "pkg/service.py", """
            RPC_CONTRACTS = {
                "FinishedWork": {"timeout_s": 1.0, "idempotent": False},
                "Ping": {"timeout_s": 1.0, "idempotent": True},
            }
        """)
    body = "RPC_ANCHORS = {\n"
    for k, v in anchors.items():
        body += f'    "{k}": "{v}",\n'
    body += "}\n\n"
    for t in transitions:
        body += f"def t_{t}(s):\n    return s\n\n"
    _write(tmp_path, "pkg/analysis/model/protocol.py", body)
    return _analyze(tmp_path, "pkg")[1]


def test_model_anchoring_clean(tmp_path):
    findings = _sc406_repo(tmp_path,
                           {"finished_work": "FinishedWork"},
                           ["finished_work"])
    assert [f for f in findings if f.code == "SC406"] == []


def test_model_anchor_without_transition_flagged(tmp_path):
    findings = _sc406_repo(tmp_path,
                           {"finished_work": "FinishedWork",
                            "ghost": "Ping"},
                           ["finished_work"])
    msgs = [f.message for f in findings if f.code == "SC406"]
    assert any("`ghost`" in m and "t_ghost" in m for m in msgs)


def test_model_anchor_without_contract_flagged(tmp_path):
    findings = _sc406_repo(tmp_path,
                           {"finished_work": "FinishedWork",
                            "extra": "NoSuchRpc"},
                           ["finished_work", "extra"])
    msgs = [f.message for f in findings if f.code == "SC406"]
    assert any("NoSuchRpc" in m and "no RPC_CONTRACTS entry" in m
               for m in msgs)


def test_model_missing_nonidempotent_rpc_flagged(tmp_path):
    """Drift the OTHER direction: an idempotent=False contract with no
    anchoring transition blinds the explorer to a mutating RPC."""
    findings = _sc406_repo(tmp_path,
                           {"ping": "Ping"},
                           ["ping"])
    msgs = [f.message for f in findings if f.code == "SC406"]
    assert any("FinishedWork" in m and "idempotent=False" in m
               for m in msgs)


def test_model_package_without_anchors_flagged(tmp_path):
    _write(tmp_path, "setup.py", "# root\n")
    _write(tmp_path, "pkg/service.py", """
        RPC_CONTRACTS = {
            "FinishedWork": {"timeout_s": 1.0, "idempotent": False},
        }
    """)
    _write(tmp_path, "pkg/analysis/model/explorer.py", "x = 1\n")
    _, findings = _analyze(tmp_path, "pkg")
    msgs = [f.message for f in findings if f.code == "SC406"]
    assert any("no RPC_ANCHORS" in m for m in msgs)


def test_real_model_anchoring_is_live():
    """The shipped analysis/model/protocol.py stays pinned to the
    shipped RPC_CONTRACTS: SC406 must fire if either side drifts."""
    from scanner_tpu.analysis.model import RPC_ANCHORS
    from scanner_tpu.engine.service import RPC_CONTRACTS
    non_idem = {r for r, c in RPC_CONTRACTS.items()
                if c.get("idempotent") is False}
    anchored = set(RPC_ANCHORS.values())
    assert non_idem <= anchored
    assert anchored <= set(RPC_CONTRACTS)
    # and the analyzer agrees (zero SC406 over the real tree)
    findings = run_analysis([os.path.join(REPO, "scanner_tpu")],
                            root=REPO, select=["SC406"])
    assert findings == []


# ---------------------------------------------------------------------------
# baseline hygiene: duplicate fingerprints
# ---------------------------------------------------------------------------

def test_baseline_rejects_duplicate_fingerprints(tmp_path):
    """A copy-pasted baseline entry silently double-counts an accepted
    exception — the loader must refuse the file outright."""
    _write(tmp_path, "s.py", SLEEPY)
    proj, findings = _analyze(tmp_path)
    res = split_findings(proj, findings)
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(bl_path, res.unsuppressed)
    doc = json.load(open(bl_path))
    doc["entries"][0]["justification"] = "legit entry"
    doc["entries"].append(dict(doc["entries"][0]))
    json.dump(doc, open(bl_path, "w"))
    with pytest.raises(BaselineError) as ei:
        load_baseline(bl_path)
    assert "duplicate fingerprint" in str(ei.value)


# ---------------------------------------------------------------------------
# --changed: restricted runs agree with full runs
# ---------------------------------------------------------------------------

def _git(cwd, *args):
    subprocess.run(["git", *args], cwd=cwd, check=True,
                   capture_output=True, text=True, timeout=60)


def _changed_repo(tmp_path):
    _write(tmp_path, "setup.py", "# root\n")
    _write(tmp_path, "scanner_tpu/__init__.py", "")
    _write(tmp_path, "scanner_tpu/mod.py", "x = 1\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "-c", "user.email=t@t", "-c", "user.name=t",
         "commit", "-q", "--allow-empty", "-m", "seed")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "-c", "user.email=t@t", "-c", "user.name=t",
         "commit", "-q", "-m", "clean tree")
    return tmp_path


def _run_check(root, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scanner_check.py"),
         "--root", str(root), str(root / "scanner_tpu"),
         "--no-baseline", "--json", *extra],
        capture_output=True, text=True, env=env, timeout=120)


def test_changed_agrees_with_full_run(tmp_path):
    """--changed over a dirty checkout reports exactly the findings a
    full run reports for the touched modules."""
    root = _changed_repo(tmp_path)
    _write(root, "scanner_tpu/mod.py", SLEEPY)
    full = json.loads(_run_check(root).stdout)
    restricted = json.loads(_run_check(root, "--changed").stdout)
    assert restricted["counts"] == full["counts"] == {"SC202": 1}
    strip = [(f["code"], f["path"], f["fingerprint"])
             for f in full["findings"]]
    strip_r = [(f["code"], f["path"], f["fingerprint"])
               for f in restricted["findings"]]
    assert strip == strip_r


def test_changed_clean_tree_is_noop(tmp_path):
    root = _changed_repo(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scanner_check.py"),
         "--root", str(root), str(root / "scanner_tpu"),
         "--no-baseline", "--changed"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0
    assert "no scanner_tpu modules touched" in r.stdout


def test_changed_refuses_write_baseline(tmp_path):
    root = _changed_repo(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scanner_check.py"),
         "--root", str(root), "--changed", "--write-baseline"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 2 and "erase" in r.stderr


def test_changed_paths_fall_back_when_analyzer_touched(tmp_path):
    """A change under scanner_tpu/analysis/ affects every finding, so
    the restriction must dissolve into a full run."""
    from scanner_tpu.analysis.static import changed_paths
    root = _changed_repo(tmp_path)
    _write(root, "scanner_tpu/analysis/static/extra.py", "y = 2\n")
    assert changed_paths(str(root)) is None
