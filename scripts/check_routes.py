"""Lint: every GET route the rendezvous server serves must be listed
in the consolidated signed-GET table in ``docs/api.md``, and every
table row must name a ``run.http_client`` accessor that actually
exists.

The control plane grew one observability surface per PR (metrics,
health, membership, sanitizer, autotune, replay, projection,
serving, timeseries, alerts, events); the table in
docs/api.md#the-signed-get-surface is the one place an operator can
see them all.  This lint (tests/test_route_lint.py, tier-1 — the
check_env_vars.py pattern) makes a route that skipped the table, or a
documented route whose client accessor was renamed away, a test
failure instead of a silent drift.

Run::

    python scripts/check_routes.py            # exit 1 on any drift
    python scripts/check_routes.py --list     # dump the served inventory
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from typing import Dict, List, Set

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVER_PY = os.path.join(REPO, "horovod_tpu", "run", "http_server.py")
CLIENT_PY = os.path.join(REPO, "horovod_tpu", "run", "http_client.py")
API_MD = os.path.join(REPO, "docs", "api.md")

#: the literal route comparisons inside do_GET
_ROUTE = re.compile(r'if path == "(/[A-Za-z0-9._-]+)":')
#: the one prefix route (cursor scope reads) — documented as a family
_SCOPE_PREFIX = re.compile(r"if path\.startswith\(SCOPE_ROUTE_PREFIX\)")
SCOPE_FAMILY = "/scope/<name>"

#: a docs table row: | `GET /x` | ... http_client.get_x ... |
_DOC_ROW = re.compile(r"^\|\s*`GET (/[^`?\s]+)[^`]*`\s*\|(.*)$", re.M)
_ACCESSOR = re.compile(r"`http_client\.(\w+)`")
_DEF = re.compile(r"^def (\w+)\(", re.M)


def _do_get_body(server_path: str = SERVER_PY) -> str:
    """The source of do_GET only — do_POST/do_PUT route on constants
    and prefixes, but scoping the parse keeps the lint honest if a
    literal comparison ever appears there too."""
    with open(server_path) as f:
        src = f.read()
    m = re.search(r"^(\s*)def do_GET\b.*?(?=^\1def )", src,
                  re.M | re.S)
    return m.group(0) if m else src


def routes_served(server_path: str = SERVER_PY) -> Set[str]:
    body = _do_get_body(server_path)
    routes = set(_ROUTE.findall(body))
    if _SCOPE_PREFIX.search(body):
        routes.add(SCOPE_FAMILY)
    return routes


def routes_documented(api_path: str = API_MD) -> Dict[str, str]:
    """Route → its table row text (docs/api.md signed-GET table)."""
    with open(api_path) as f:
        text = f.read()
    out: Dict[str, str] = {}
    for route, rest in _DOC_ROW.findall(text):
        out.setdefault(route, rest)
    return out


def accessors_defined(client_path: str = CLIENT_PY) -> Set[str]:
    with open(client_path) as f:
        return set(_DEF.findall(f.read()))


def drift(server_path: str = SERVER_PY, api_path: str = API_MD,
          client_path: str = CLIENT_PY) -> List[str]:
    """Every divergence between the served routes, the docs table, and
    the client accessors, as human-readable complaint lines."""
    served = routes_served(server_path)
    documented = routes_documented(api_path)
    defined = accessors_defined(client_path)
    problems: List[str] = []
    for route in sorted(served - set(documented)):
        problems.append(
            f"route {route} is served by do_GET but missing from the "
            f"signed-GET table in docs/api.md")
    for route in sorted(set(documented) - served):
        problems.append(
            f"route {route} is documented in docs/api.md but do_GET "
            f"does not serve it (stale row?)")
    for route in sorted(served & set(documented)):
        accessors = _ACCESSOR.findall(documented[route])
        if not accessors:
            problems.append(
                f"docs row for {route} names no `http_client.<fn>` "
                f"accessor")
            continue
        for fn in accessors:
            if fn not in defined:
                problems.append(
                    f"docs row for {route} names http_client.{fn}, "
                    f"which run/http_client.py does not define")
    return problems


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--list", action="store_true",
                   help="print the served route inventory and exit")
    args = p.parse_args(argv)
    if args.list:
        for route in sorted(routes_served()):
            print(route)
        return 0
    problems = drift()
    if not problems:
        print(f"check_routes: OK — {len(routes_served())} GET routes "
              "served, all documented with live accessors")
        return 0
    for line in problems:
        print(f"DRIFT: {line}", file=sys.stderr)
    print(f"check_routes: {len(problems)} route-inventory problem(s)",
          file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
