"""Test harness config: run on a virtual 8-device CPU mesh with x64.

Mirrors SURVEY.md §7: sharding is tested on a CPU mesh
(xla_force_host_platform_device_count), and golden tests compare against
float64 NumPy reference implementations — so tests enable x64. The TPU bench
path (bench.py) runs float32 on the real chip instead.

Env vars must be set before jax is imported anywhere.
"""
import os

# Force CPU, by name: the x64 golden tests and the 8-device virtual mesh
# are CPU-only concerns, a chip on this host is left free for chip runs,
# and a CPU that was asked for by name is where the Pallas backends run
# in the interpreter (ops/cd_pallas.interpret_default).  The config is
# set after import too, for an embedding that imported jax before us.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# The persistent compilation cache: the tests set nothing.  jax is
# imported above, before the package, so the package's default directory
# (bluesky_tpu/__init__.py) does not reach this process; workers that a
# test spawns do get it; and a JAX_COMPILATION_CACHE_DIR set from outside
# is read by jax itself.  An earlier note here described a segfault in
# deserialize_executable (jax 0.9.0, cached shard_map / lax.cond Pallas
# programs, under xdist).  PR 21 looked for it: tier-1 in one process
# with the variable set, cold then warm (every program reloaded), passed
# both times without a crash, so the cache is not switched off here.

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_log_path(tmp_path, monkeypatch):
    """Route ALL file output (MAKEDOC, DUMPRTE, datalog CSV logs) into the
    test's tmp dir: a full pytest run must leave `git status` clean
    (VERDICT r2 'test-run hygiene').  Tests that assert on specific log
    locations re-patch settings.log_path on top of this."""
    from bluesky_tpu import settings
    monkeypatch.setattr(settings, "log_path", str(tmp_path / "output"))


# ---------------------------------------------------------------------------
# Standalone-data story (VERDICT r2 #8): the suite must pass with the
# read-only reference mount absent.  Tests that consume the mount — the
# golden oracles importing the actual reference source, the real navdata/
# performance databases, the scenario library, and the source-parsing
# coverage tests — skip with a clear reason instead of erroring.
# Simulate an absent mount with BLUESKY_TPU_NO_REF=1.
REF_MOUNT = "/root/reference"
REF_PRESENT = (os.path.isdir(REF_MOUNT)
               and os.environ.get("BLUESKY_TPU_NO_REF") != "1")

_REF_DEPENDENT_FILES = {
    "test_golden_reference.py",    # imports the reference CD/MVP source
    "test_openap_real.py",         # value-for-value vs reference coeff DB
    "test_perf_models.py",         # BS XML + BADA parser golden tests
    "test_resolvers.py",           # ref_oracle golden comparisons
    "test_cr_mvp_ref.py",          # imports the reference MVP source
    "test_guiclient_ref.py",       # imports the reference Qt client source
    "test_command_coverage.py",    # parses the reference stack source
    "test_stream_schema.py",       # parses the reference screenio source
    "test_navdb.py",               # real 11 MB navdata
    "test_fms_scenarios.py",       # reference scenario files
    "test_scenario_library.py",    # reference scenario library
}


# collect_ignore (not a skip marker): the golden-oracle modules import
# the reference SOURCE at module import time, so they must not even be
# collected when the mount is gone.
collect_ignore = [] if REF_PRESENT else sorted(_REF_DEPENDENT_FILES)
