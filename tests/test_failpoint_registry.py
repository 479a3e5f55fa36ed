"""Failpoint registry consistency (ISSUE 13 satellite).

Chaos scenarios reference failpoint SITES by string name — a rename on
either side silently turns the scenario into a no-op (the arm never
fires, `hits` guards notwithstanding the suite only notices if every
scenario carries one). This test closes both directions statically:

* every name ARMED anywhere in tests/tools must exist as a
  literal injection site in ``seaweedfs_tpu/`` (or be a valid dynamic
  ``pb.<Method>`` point — those are synthesized per RPC in pb/rpc.py);
* every literal site in ``seaweedfs_tpu/`` must be armed by at least
  one test/tool — a site nothing exercises is dead chaos surface that
  would rot unnoticed.
"""

import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "seaweedfs_tpu"

# injection-site verbs, as called at sites (possibly split over lines);
# `torn` is the ISSUE-16 partial-write verb, `is_armed` gates hot paths
_SITE_RE = re.compile(
    r'failpoint\.(?:fail|delay|corrupt|torn|is_armed)\(\s*"([a-z0-9._]+)"')
# programmatic arming in tests/tools
_ARM_RE = re.compile(
    r'failpoint\.(?:active|configure)\(\s*"([a-zA-Z0-9._]+)"')
# SWFS_FAILPOINTS / load_env spec items: <name>=<mode>(
_SPEC_RE = re.compile(
    r'([a-zA-Z][a-zA-Z0-9._]*)=(?:error|delay|corrupt|crash|torn)\(')


def _scan(paths, regexes):
    found: set[str] = set()
    for path in paths:
        text = path.read_text(encoding="utf-8", errors="replace")
        for rx in regexes:
            found.update(rx.findall(text))
    return found


def _sites() -> set[str]:
    files = [p for p in PKG.rglob("*.py")
             # the failpoint module's own docstring shows example calls;
             # they are documentation, not injection sites
             if p.name != "failpoint.py"]
    return _scan(files, [_SITE_RE])


def _armed() -> set[str]:
    files = list((REPO / "tests").glob("*.py"))
    files += list((REPO / "tools").glob("*.py"))
    return _scan(files, [_ARM_RE, _SPEC_RE])


def _pb_methods() -> set[str]:
    text = (PKG / "pb" / "rpc.py").read_text()
    return set(re.findall(r'_m\("([A-Za-z]+)"', text))


def test_every_armed_failpoint_has_a_live_site():
    sites = _sites()
    methods = _pb_methods()
    bogus = set()
    for name in _armed():
        if name.startswith("pb."):
            if name[3:] not in methods:
                bogus.add(name)
        elif name not in sites:
            bogus.add(name)
    assert not bogus, (
        f"failpoints armed in tests/tools with NO matching injection "
        f"site in seaweedfs_tpu/ (renamed site? typo?): {sorted(bogus)}")


def test_every_site_is_exercised_somewhere():
    armed = _armed()
    dead = {name for name in _sites() if name not in armed}
    assert not dead, (
        f"failpoint sites never armed by any test/tool — dead chaos "
        f"surface that would rot unnoticed: {sorted(dead)}")


def test_scans_are_not_vacuous():
    """The regexes must keep matching the real call shapes — an empty
    scan would make both directions trivially pass."""
    sites = _sites()
    armed = _armed()
    assert len(sites) >= 10, sites
    assert len(armed) >= 10, armed
    assert "scrub.gather.range" in sites  # the ISSUE-13 site
    assert "volume.http.read" in sites
    assert any(a.startswith("pb.") for a in armed)
