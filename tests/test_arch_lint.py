"""Architectural lint driver (dylint-equivalent enforcement, SURVEY §2.5).

The checks themselves moved onto the fabric-lint engine
(cyberfabric_core_tpu/apps/fabric_lint/rules/design.py) — this file is the
thin pytest driver that keeps every family green on the live package, with
one failing fixture per family (dylint ui-test parity). Rule mapping:

DE01  layer purity: L1 modkit never imports upward (gateway/, modules/);
      L3 the compute tier (models/, ops/, parallel/) never imports the
      serving tier — kernels stay host-framework-free.
DE02  L2 sqlite3 is touched ONLY by the modkit DB boundary — "no plain SQL
      outside the secure ORM" (reference: advisory_locks.rs:6-9 policy).
DE03  domain purity: DE0301 no-infra / DE0308 no-transport in runtime/,
      models/, ops/, parallel/; DE0309 domain data types are @dataclass.
DE04  L4 business modules use only the gateway's public seams
      (gateway.middleware, gateway.validation; *Api contract types).
DE05  client layer: DE0503 Api-suffixed SDK traits + contract-typed hub
      resolution, DE0504 versioned service contracts, L5 modules talk
      through ClientHub SDK traits (.sdk).
DE07  security: raw-connection escape hatches confined; SecretString never
      string-formatted.
DE08  REST conventions. DE09 GTS identifier validity. DE13 no print().
EC01  error codes come from the catalog; every namespace referenced.

The AS/JP/LK semantic families live in tests/test_fabric_lint.py.
"""

from functools import lru_cache
from pathlib import Path

from cyberfabric_core_tpu.apps.fabric_lint import Engine, all_rules

PKG = Path(__file__).resolve().parents[1] / "cyberfabric_core_tpu"

_DESIGN_FAMILIES = ("DE", "EC")


@lru_cache(maxsize=1)
def _repo_findings():
    """One engine pass over the live package, shared by every test here."""
    engine = Engine(all_rules()).select(_DESIGN_FAMILIES)
    return tuple(f for f in engine.run(PKG) if not f.suppressed)


def _findings(rule: str, contains: str = "", path_prefix: str = ""):
    return [f for f in _repo_findings()
            if f.rule == rule and contains in f.message
            and f.path.startswith(path_prefix)]


def _fmt(findings):
    return "\n".join(f"{f.path}:{f.line} {f.rule} {f.message}"
                     for f in findings)


def _lint_snippet(source: str, relpath: str, tier: str, select=("DE", "EC")):
    engine = Engine(all_rules()).select(select)
    return [f for f in engine.run_source(source, relpath=relpath, tier=tier)
            if not f.suppressed]


# ----------------------------------------------------------- layer purity


def test_L1_modkit_never_imports_upward():
    bad = _findings("DE01", path_prefix="modkit/")
    assert not bad, f"modkit imports upward:\n{_fmt(bad)}"


def test_L2_sqlite_only_in_db():
    """Driver imports live in the engine layer only (db_engine.py owns the
    backends; db.py owns the secure ORM above them)."""
    bad = _findings("DE02")
    assert not bad, f"sqlite3 outside the modkit DB boundary:\n{_fmt(bad)}"


def test_L3_compute_tier_is_serving_free():
    for tier in ("models", "ops", "parallel"):
        bad = _findings("DE01", path_prefix=f"{tier}/")
        assert not bad, f"compute tier {tier}/ imports serving tier:\n{_fmt(bad)}"


def test_L4_modules_use_only_public_gateway_seams():
    bad = _findings("DE04")
    assert not bad, (
        "modules may import only gateway.middleware/gateway.validation "
        f"(or *Api contracts):\n{_fmt(bad)}")


def test_L5_cross_module_calls_go_through_sdk():
    bad = _findings("DE05", contains="cross-module")
    assert not bad, (
        f"cross-module implementation imports (use ClientHub/.sdk):\n{_fmt(bad)}")


def test_L1_fixture_fails():
    bad = _lint_snippet(
        "from cyberfabric_core_tpu.gateway import router\n",
        relpath="modkit/helper.py", tier="modkit", select=("DE01",))
    assert [f.rule for f in bad] == ["DE01"]


# --------------------------------------------------------------- security


def test_L6_security_raw_connection_confined():
    """DE07 equivalent (security lint): the raw-connection escape hatches
    (`raw_connection()`, `raw_for_migrations()`) are callable only inside the
    modkit DB boundary — 'no plain SQL outside migrations'."""
    bad = _findings("DE07", contains="raw DB connection")
    assert not bad, f"raw DB connection access outside modkit/db:\n{_fmt(bad)}"


def test_L6_secret_string_never_interpolated():
    """DE07 equivalent: SecretString.expose() is the only sanctioned reveal,
    and it must never feed a string-formatting expression directly."""
    bad = _findings("DE07", contains="SecretString")
    assert not bad, f"SecretString revealed inside string formatting:\n{_fmt(bad)}"


def test_L6_fixture_fails():
    bad = _lint_snippet(
        'def show(s):\n    return f"key={s.expose()}"\n',
        relpath="modules/m.py", tier="modules", select=("DE07",))
    assert [f.rule for f in bad] == ["DE07"]


# ------------------------------------------------------- REST conventions


def test_L7_rest_route_conventions():
    """DE08 equivalent: every registered route uses a known HTTP verb, is
    rooted at /v1/ (or a sanctioned infra path), has no trailing slash, and
    uses lowercase kebab/snake segments with {snake_case} params."""
    bad = _findings("DE08")
    assert not bad, f"REST convention violations:\n{_fmt(bad)}"


def test_L7_fixture_fails():
    bad = _lint_snippet(
        'def reg(api):\n'
        '    api.operation("GET", "/legacy/Thing/")\n',
        relpath="modules/m.py", tier="modules", select=("DE08",))
    assert len(bad) >= 2  # not /v1/-rooted AND trailing slash AND bad casing


# ---------------------------------------------------------- error catalog


def test_EC01_error_codes_come_from_the_catalog():
    """EC01 (declare_errors! parity): Problem/ProblemError call sites must
    not invent error codes as string literals — codes live in
    modkit/catalogs/errors.json and are referenced via errcat.ERR."""
    bad = _findings("EC01", contains="literal error code")
    assert not bad, f"literal error codes found:\n{_fmt(bad)}"


def test_EC01_catalog_codes_are_actually_used():
    """The inverse direction: every catalog namespace is referenced somewhere
    (a dead namespace means the catalog and the code drifted apart)."""
    bad = _findings("EC01", contains="never referenced")
    assert not bad, f"catalog namespaces never referenced:\n{_fmt(bad)}"


def test_EC01_fixture_fails():
    bad = _lint_snippet(
        'def boom(Problem):\n'
        '    raise Problem(code="made_up_code", title="nope")\n',
        relpath="modules/m.py", tier="modules", select=("EC01",))
    assert [f.rule for f in bad] == ["EC01"]


# -------------------------------------------------------------------- DE03


def test_DE03_domain_tiers_are_transport_and_infra_free():
    bad = _findings("DE03", contains="DE030")  # DE0301 + DE0308
    assert not bad, f"domain tier violates DE03:\n{_fmt(bad)}"


def test_DE03_fixture_fails():
    """The rule actually fires (dylint ui-test parity): a domain file that
    imports aiohttp or sqlite3 must be flagged."""
    bad = _lint_snippet(
        "import aiohttp\nimport sqlite3\n",
        relpath="runtime/domain_mod.py", tier="runtime", select=("DE03",))
    assert len(bad) == 2, _fmt(bad)


def test_DE03_domain_data_types_are_dataclasses():
    bad = _findings("DE03", contains="DE0309")
    assert not bad, f"domain data types missing @dataclass (DE0309):\n{_fmt(bad)}"


def test_DE03_model_fixture_fails():
    bad = _lint_snippet(
        "class FooConfig:\n    pass\n",
        relpath="runtime/m.py", tier="runtime", select=("DE03",))
    assert len(bad) == 1 and "FooConfig" in bad[0].message


# -------------------------------------------------------------------- DE05


def test_DE05_sdk_traits_use_the_api_suffix():
    bad = _findings("DE05", contains="DE0503 SDK trait")
    assert not bad, f"SDK traits without the Api suffix (DE0503):\n{_fmt(bad)}"


def test_DE05_suffix_fixture_fails():
    bad = _lint_snippet(
        "class ThingPluginClient:\n    def call(self): ...\n",
        relpath="modules/sdk.py", tier="modules", select=("DE05",))
    assert len(bad) == 1 and "ThingPluginClient" in bad[0].message


def test_DE05_hub_resolution_uses_contract_types():
    """hub.get/try_get must resolve *Api contract types only — resolving a
    concrete class through the hub bypasses the SDK seam."""
    bad = _findings("DE05", contains="hub resolution")
    assert not bad, f"ClientHub resolution of non-contract types:\n{_fmt(bad)}"


def test_DE05_grpc_service_contracts_are_versioned():
    bad = _findings("DE05", contains="DE0504")
    assert not bad, f"unversioned gRPC service contracts (DE0504):\n{_fmt(bad)}"


def test_DE05_version_fixture_fails():
    bad = _lint_snippet(
        'FOO_SERVICE = "foo.FooService"\n',
        relpath="modules/svc.py", tier="modules", select=("DE05",))
    assert len(bad) == 1 and "FOO_SERVICE" in bad[0].message


# -------------------------------------------------------------------- DE09


def test_DE09_gts_literals_in_source_are_valid():
    bad = _findings("DE09")
    assert not bad, f"malformed GTS identifiers in source (DE0901):\n{_fmt(bad)}"


def test_DE09_fixture_fails():
    bad = _lint_snippet(
        'X = "gts.x.core.Bad_Vendor.thing.v1~"\n',
        relpath="modules/g.py", tier="modules", select=("DE09",))
    assert len(bad) == 1 and "Bad_Vendor" in bad[0].message


# -------------------------------------------------------------------- DE13


def test_DE13_no_print_in_production_code():
    bad = _findings("DE13")
    assert not bad, f"print() in production code — use logging (DE1301):\n{_fmt(bad)}"


def test_DE13_fixture_fails():
    bad = _lint_snippet(
        'print("leak")\n'
        'if __name__ == "__main__":\n    print("ok: CLI surface")\n',
        relpath="modules/p.py", tier="modules", select=("DE13",))
    assert [(f.rule, f.line) for f in bad] == [("DE13", 1)]


def test_the_gates_name_no_file_that_is_not_in_the_tree():
    """``make safety`` and CI run files by name: a ``.py`` file or a
    ``tests/`` path the Makefile or the workflow names exists, so a deletion
    that leaves a target behind fails here and not on the next release."""
    import re

    root = PKG.parent
    named = re.compile(r"(?<![\w./-])((?:[\w-]+/)*[\w-]+\.py|tests/[\w./-]+)")
    missing = []
    for gate in ("Makefile", ".github/workflows/ci.yml"):
        paths = set(named.findall((root / gate).read_text()))
        assert paths, f"{gate} names no file: the pattern went stale"
        missing += [f"{gate}: {p}" for p in sorted(paths)
                    if not (root / p).exists()]
    assert missing == []
