"""scripts/check_routes.py: the signed-GET route inventory lint, run
from tier-1 so a route added to the rendezvous server without a row in
docs/api.md (or a documented accessor that was renamed away) fails
fast instead of drifting silently."""

import importlib.util as _ilu
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "check_routes.py")


def _load():
    spec = _ilu.spec_from_file_location("check_routes", SCRIPT)
    mod = _ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAKE_SERVER = textwrap.dedent('''\
    class H:
        def do_GET(self):
            if path.startswith(SCOPE_ROUTE_PREFIX):
                return
            if path == "/health":
                return
            if path == "/events":
                return

        def do_POST(self):
            if path == "/not-a-get-route":
                return
''')

FAKE_CLIENT = textwrap.dedent('''\
    def get_health(addr, port):
        pass


    def get_events(addr, port):
        pass


    def get_scope(addr, port):
        pass
''')

FAKE_DOCS = textwrap.dedent('''\
    | route | scope | producer | accessor | console |
    |---|---|---|---|---|
    | `GET /health` | leases | heartbeats | `http_client.get_health` | dash |
    | `GET /events` | events | recorder | `http_client.get_events` | hvd_events |
    | `GET /scope/<name>?since=` | any | writers | `http_client.get_scope` | relays |
''')


def _fake_tree(tmp_path, server=FAKE_SERVER, client=FAKE_CLIENT,
               docs=FAKE_DOCS):
    sp = tmp_path / "http_server.py"
    cp = tmp_path / "http_client.py"
    dp = tmp_path / "api.md"
    sp.write_text(server)
    cp.write_text(client)
    dp.write_text(docs)
    return str(sp), str(dp), str(cp)


def test_repo_routes_all_documented_with_live_accessors():
    mod = _load()
    problems = mod.drift()
    assert not problems, "\n".join(problems)


def test_repo_inventory_includes_every_observability_route():
    mod = _load()
    served = mod.routes_served()
    for route in ("/metrics", "/health", "/membership", "/sanitizer",
                  "/autotune", "/replay", "/projection",
                  "/serving", "/timeseries", "/alerts", "/events"):
        assert route in served, f"{route} not parsed from do_GET"


def test_lint_passes_on_consistent_fake_tree(tmp_path):
    mod = _load()
    sp, dp, cp = _fake_tree(tmp_path)
    assert mod.drift(server_path=sp, api_path=dp, client_path=cp) == []


def test_lint_flags_undocumented_route(tmp_path):
    mod = _load()
    server = FAKE_SERVER.replace(
        'if path == "/events":',
        'if path == "/brand-new":\n                return\n'
        '            if path == "/events":')
    sp, dp, cp = _fake_tree(tmp_path, server=server)
    problems = mod.drift(server_path=sp, api_path=dp, client_path=cp)
    assert any("/brand-new" in p and "missing from" in p
               for p in problems), problems


def test_lint_flags_stale_doc_row_and_dead_accessor(tmp_path):
    mod = _load()
    docs = FAKE_DOCS + \
        "| `GET /gone` | x | y | `http_client.get_gone` | z |\n"
    client = FAKE_CLIENT.replace("def get_events", "def fetch_events")
    sp, dp, cp = _fake_tree(tmp_path, client=client, docs=docs)
    problems = mod.drift(server_path=sp, api_path=dp, client_path=cp)
    assert any("/gone" in p and "stale" in p for p in problems), problems
    assert any("get_events" in p and "does not define" in p
               for p in problems), problems


def test_lint_flags_row_without_accessor(tmp_path):
    mod = _load()
    docs = FAKE_DOCS.replace("`http_client.get_events`", "(none)")
    sp, dp, cp = _fake_tree(tmp_path, docs=docs)
    problems = mod.drift(server_path=sp, api_path=dp, client_path=cp)
    assert any("/events" in p and "no `http_client" in p
               for p in problems), problems


def test_lint_ignores_post_only_literal_routes(tmp_path):
    mod = _load()
    sp, dp, cp = _fake_tree(tmp_path)
    assert "/not-a-get-route" not in mod.routes_served(sp)


def test_cli_exit_codes():
    ok = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                        text=True, timeout=120)
    assert ok.returncode == 0, ok.stderr
    assert "OK" in ok.stdout
