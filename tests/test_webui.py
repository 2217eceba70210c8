"""WebUI smoke test: the master serves the SPA, and the exact API sequence
the app makes (login → experiments → detail → trials → metrics → agents →
job queue) returns the shapes the JS consumes.

Reference: webui/react served by the Go master; no browser ships in the test
image, so this drives the app's own request sequence over HTTP. (Manual
browser pass: see .claude/skills/verify.)"""

import json
import os
import re
import time
import urllib.error
import urllib.request

import pytest

from tests.test_platform_e2e import (  # noqa: F401
    Devcluster,
    FIXTURES,
    _create_experiment,
    _experiment_config,
    _wait_experiment,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    yield c
    c.stop()


def _get(url, content_type=None):
    with urllib.request.urlopen(url, timeout=10) as r:
        if content_type:
            assert r.headers.get("Content-Type", "").startswith(content_type)
        return r.read().decode()


def test_static_serving(cluster):
    html = _get(cluster.master_url + "/", "text/html")
    assert "<title>determined-tpu</title>" in html
    # assets referenced by the shell exist and carry correct types
    for ref, ctype in (("/ui/app.js", "application/javascript"),
                       ("/ui/style.css", "text/css")):
        assert ref in html
        body = _get(cluster.master_url + ref, ctype)
        assert body.strip()
    # traversal is rejected
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(cluster.master_url + "/ui/../master/db.cc")
    assert ei.value.code == 404


def test_app_api_sequence(cluster, tmp_path):
    """Every endpoint + field the SPA reads, end-to-end with a real run."""
    eid, token = _create_experiment(
        cluster, _experiment_config(tmp_path), activate=True)
    _wait_experiment(cluster, eid, token)

    exps = cluster.api("GET", "/api/v1/experiments", token=token)["experiments"]
    e = next(x for x in exps if x["id"] == eid)
    assert e["name"] == "e2e-fixture"
    assert e["state"] == "COMPLETED"
    assert e["config"]["searcher"]["name"] == "single"

    detail = cluster.api(
        "GET", f"/api/v1/experiments/{eid}", token=token)["experiment"]
    assert detail["config"]["resources"]["slots_per_trial"] == 1

    trials = cluster.api(
        "GET", f"/api/v1/experiments/{eid}/trials", token=token)["trials"]
    assert trials and trials[0]["state"] == "COMPLETED"

    metrics = cluster.api(
        "GET", f"/api/v1/trials/{trials[0]['id']}/metrics", token=token
    )["metrics"]
    # the chart builder needs group_name, total_batches, numeric metrics
    train_pts = [(m["total_batches"], m["metrics"].get("loss"))
                 for m in metrics if m["group_name"] == "training"]
    assert train_pts and all(
        isinstance(x, int) and isinstance(y, float) for x, y in train_pts)
    val_pts = [m for m in metrics if m["group_name"] == "validation"]
    assert val_pts and "val_loss" in val_pts[-1]["metrics"]

    agents = cluster.api("GET", "/api/v1/agents", token=token)["agents"]
    assert agents[0]["slots"] and {"id", "enabled", "allocation_id"} <= set(
        agents[0]["slots"][0])

    jobs = cluster.api("GET", "/api/v1/job-queues", token=token)["jobs"]
    assert isinstance(jobs, list)  # drained after completion


def test_trial_log_viewer_flow(cluster, tmp_path):
    """The trial page's log viewer: paged fetch by offset, then a follow
    long-poll that returns promptly once lines exist (reference TrialLogs)."""
    eid, token = _create_experiment(
        cluster, _experiment_config(tmp_path), activate=True)
    _wait_experiment(cluster, eid, token)
    trials = cluster.api(
        "GET", f"/api/v1/experiments/{eid}/trials", token=token)["trials"]
    tid = trials[0]["id"]

    # trial metadata the page header reads
    t = cluster.api("GET", f"/api/v1/trials/{tid}", token=token)["trial"]
    assert t["experiment_id"] == eid and t["total_batches"] >= 8

    # paged fetch exactly as the viewer does
    offset, lines = 0, []
    while True:
        logs = cluster.api(
            "GET",
            f"/api/v1/tasks/trial-{tid}/logs?offset={offset}&follow=false",
            token=token)["logs"]
        if not logs:
            break
        for line in logs:
            offset = max(offset, line["id"])
            lines.append(line["log"])
        assert all({"id", "log"} <= set(line) for line in logs)
    assert any("trial complete" in line for line in lines)

    # follow=true from a fresh offset returns immediately with data
    logs = cluster.api(
        "GET",
        f"/api/v1/tasks/trial-{tid}/logs?offset=0&follow=true"
        f"&timeout_seconds=5",
        token=token)["logs"]
    assert logs


def test_hp_search_view_data(cluster, tmp_path):
    """The experiment page's HP table + hparam-vs-metric scatter need per-
    trial hparams and searcher_metric_value from an adaptive search."""
    searcher = {
        "name": "adaptive_asha", "metric": "val_loss",
        "max_length": {"batches": 8}, "max_trials": 4, "max_rungs": 2,
        "divisor": 2, "max_concurrent_trials": 2,
    }
    config = _experiment_config(
        tmp_path, searcher=searcher,
        extra={"hyperparameters": {"lr": {"type": "log", "minval": -2,
                                          "maxval": 0}}})
    eid, token = _create_experiment(cluster, config, activate=True)
    _wait_experiment(cluster, eid, token, timeout=180.0)
    trials = cluster.api(
        "GET", f"/api/v1/experiments/{eid}/trials", token=token)["trials"]
    assert len(trials) == 4
    scored = [t for t in trials if t.get("searcher_metric_value") is not None]
    assert len(scored) >= 2, "scatter needs >=2 scored trials"
    for t in scored:
        assert isinstance(t["hparams"].get("lr"), float)
    # distinct sampled hparams → a real scatter, not a vertical line
    assert len({t["hparams"]["lr"] for t in scored}) >= 2
    # trial-comparison chart data: per-trial validation series exist, and
    # ASHA rung geometry shows as different curve lengths across trials
    lengths = set()
    for t in trials:
        vm = cluster.api(
            "GET", f"/api/v1/trials/{t['id']}/metrics?group=validation",
            token=token)["metrics"]
        assert vm, f"trial {t['id']} has no validation series"
        assert all("val_loss" in m["metrics"] for m in vm)
        lengths.add(max(m["total_batches"] for m in vm))
    assert len(lengths) >= 2, f"expected distinct rung lengths, got {lengths}"


def test_stream_live_update_contract(cluster, tmp_path):
    """The list page's live refresh: an experiment state change surfaces as
    a stream event the follower can react to."""
    token = cluster.login()
    out = cluster.api(
        "GET", "/api/v1/stream?since=0&timeout_seconds=0", token=token)
    since = out["latest_seq"]
    eid, token = _create_experiment(
        cluster, _experiment_config(tmp_path), activate=True)
    _wait_experiment(cluster, eid, token)
    out = cluster.api(
        "GET",
        f"/api/v1/stream?since={since}&entities=experiments"
        f"&timeout_seconds=5",
        token=token)
    assert any(e["entity"] == "experiments" and e["payload"]["id"] == eid
               for e in out["events"])


def test_user_admin_page_data(cluster):
    """The users/admin page's API sequence: list users + me + assignments,
    admin mutations (create / role change / deactivate / grant / revoke)."""
    admin = cluster.login("admin")
    me = cluster.api("GET", "/api/v1/me", token=admin)["user"]
    assert me["role"] == "admin"
    cluster.api("POST", "/api/v1/users",
                {"username": "ui-user", "role": "viewer"}, token=admin)
    users = cluster.api("GET", "/api/v1/users", token=admin)["users"]
    u = next(x for x in users if x["username"] == "ui-user")
    assert u["role"] == "viewer" and u["active"] == 1
    cluster.api("PATCH", f"/api/v1/users/{u['id']}", {"role": "user"},
                token=admin)
    grant = cluster.api("POST", "/api/v1/rbac/assignments",
                        {"role": "editor", "user_id": u["id"],
                         "workspace_id": 1}, token=admin)
    rows = cluster.api("GET", "/api/v1/rbac/assignments",
                       token=admin)["assignments"]
    assert any(r["id"] == grant["id"] and r["username"] == "ui-user"
               for r in rows)
    cluster.api("DELETE", f"/api/v1/rbac/assignments/{grant['id']}",
                token=admin)
    cluster.api("PATCH", f"/api/v1/users/{u['id']}", {"active": False},
                token=admin)
    users = cluster.api("GET", "/api/v1/users", token=admin)["users"]
    assert next(x for x in users if x["id"] == u["id"])["active"] == 0


def test_app_js_references_real_endpoints(cluster):
    """Static check: every /api/v1 path in app.js is routed by the master
    (no dead fetches shipped in the UI)."""
    js = _get(cluster.master_url + "/ui/app.js")
    token = cluster.login()
    paths = set(re.findall(r'"(/api/v1/[a-z\-/]+)', js))
    assert paths  # sanity
    for p in paths:
        if p.startswith("/api/v1/auth"):
            continue  # POST-only; covered by login itself
        status = 0
        req = urllib.request.Request(
            cluster.master_url + p,
            headers={"Authorization": f"Bearer {token}"})
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                status = r.status
        except urllib.error.HTTPError as e:
            status = e.code
        assert status == 200, f"{p} -> {status}"


def test_tasks_page_and_kill_flow(cluster):
    """The Tasks page's API sequence: list → kill (per-kind route) →
    state reflects the outcome (VERDICT r4 #9 NTSC/tasks page)."""
    token = cluster.login()
    tid = cluster.api("POST", "/api/v1/commands",
                      {"config": {"entrypoint": "sleep 600"}},
                      token=token)["id"]
    tasks = cluster.api("GET", "/api/v1/tasks", token=token)["tasks"]
    mine = [t for t in tasks if t["id"] == tid]
    assert mine and mine[0]["type"] == "COMMAND"
    # the kill button's route for COMMAND
    cluster.api("POST", f"/api/v1/commands/{tid}/kill", token=token)
    import time as _t
    deadline = _t.time() + 30
    while _t.time() < deadline:
        t = cluster.api("GET", f"/api/v1/commands/{tid}", token=token)["task"]
        if t["state"] in ("COMPLETED", "ERROR", "CANCELED"):
            break
        _t.sleep(0.2)
    assert t["state"] in ("COMPLETED", "ERROR", "CANCELED")


def test_admin_page_webhook_template_flow(cluster):
    """The Admin page's API sequence: webhook + template CRUD."""
    admin = cluster.login("admin")
    hook = cluster.api("POST", "/api/v1/webhooks",
                       {"url": "http://127.0.0.1:1/x",
                        "triggers": [{"trigger_type":
                                      "EXPERIMENT_STATE_CHANGE",
                                      "condition": {"state": "COMPLETED"}}]},
                       token=admin)
    hid = hook.get("id") or hook.get("webhook", {}).get("id")
    hooks = cluster.api("GET", "/api/v1/webhooks", token=admin)["webhooks"]
    assert any(h["id"] == hid for h in hooks)
    cluster.api("DELETE", f"/api/v1/webhooks/{hid}", token=admin)

    cluster.api("POST", "/api/v1/templates",
                {"name": "ui-tpl",
                 "config": {"resources": {"slots_per_trial": 2}}},
                token=admin)
    tpls = cluster.api("GET", "/api/v1/templates", token=admin)["templates"]
    assert any(t["name"] == "ui-tpl" for t in tpls)
    cluster.api("DELETE", "/api/v1/templates/ui-tpl", token=admin)


def test_experiments_pagination(cluster, tmp_path):
    """Server-side pagination the experiments page rides: limit/offset +
    total (VERDICT r4 #9: no list endpoint rendered whole)."""
    token = None
    for i in range(5):
        cfg = _experiment_config(tmp_path)
        cfg["name"] = f"pg-{i}"
        _, token = _create_experiment(cluster, cfg, activate=False)
    page1 = cluster.api("GET", "/api/v1/experiments?limit=2&offset=0",
                        token=token)
    assert len(page1["experiments"]) == 2
    assert page1["pagination"]["total"] == 5
    page3 = cluster.api("GET", "/api/v1/experiments?limit=2&offset=4",
                        token=token)
    assert len(page3["experiments"]) == 1
    ids = {e["id"] for e in page1["experiments"]} | \
        {e["id"] for e in page3["experiments"]}
    assert len(ids) == 3  # pages don't overlap


def test_model_version_detail_flow(cluster, tmp_path):
    """Model registry version rows expand to the backing checkpoint —
    the page's API sequence: versions → checkpoint detail."""
    eid, token = _create_experiment(
        cluster, _experiment_config(tmp_path), activate=True)
    _wait_experiment(cluster, eid, token)
    cps = cluster.api("GET", f"/api/v1/experiments/{eid}/checkpoints",
                      token=token)["checkpoints"]
    # Only COMMITTED checkpoints register (docs/serving.md "Model
    # lifecycle" — a version is a serving promise, PARTIALs refuse).
    cps = [c for c in cps if c["state"] == "COMPLETED"]
    assert cps
    cluster.api("POST", "/api/v1/models",
                {"name": "ui-model", "description": "", "metadata": {},
                 "labels": []}, token=token)
    cluster.api("POST", "/api/v1/models/ui-model/versions",
                {"checkpoint_uuid": cps[0]["uuid"], "metadata": {}},
                token=token)
    versions = cluster.api("GET", "/api/v1/models/ui-model/versions",
                           token=token)["model_versions"]
    assert versions
    ck = cluster.api(
        "GET", f"/api/v1/checkpoints/{versions[0]['checkpoint_uuid']}",
        token=token)["checkpoint"]
    assert ck["uuid"] == cps[0]["uuid"]
    assert "steps_completed" in ck


# ---------------------------------------------------------------------------
# WebUI JS execution harness (VERDICT weak #4). No JS engine ships in the
# test image, so the JS is "executed" at the data-binding level: the
# generated api_client.js is parsed into its operation table and checked
# against the served OpenAPI document, every `API.x(...)` call site in
# app.js must resolve to a generated operation, and the fields each view
# function dereferences on API payloads are EXTRACTED FROM THE JS SOURCE
# and asserted present on live master responses — if app.js starts
# reading a field the API stopped (or never started) serving, these fail.
# ---------------------------------------------------------------------------

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CLIENT_OP_RE = re.compile(
    r"^\s*(?P<name>\w+): \((?P<args>[^)]*)\) => "
    r"api\('(?P<method>[A-Z]+)', (?P<path>[`'][^`']+[`'])",
    re.M)


def _js(name):
    with open(os.path.join(REPO_ROOT, "webui", name)) as f:
        return f.read()


def _parse_api_client():
    """api_client.js → {opName: (METHOD, /api/v1/... template)} with JS
    `${x}` path params normalized back to the spec's {x} form."""
    ops = {}
    for m in _CLIENT_OP_RE.finditer(_js("api_client.js")):
        path = m.group("path").strip("`'")
        path = re.sub(r"\$\{(\w+)\}", r"{\1}", path)
        ops[m.group("name")] = (m.group("method"), path)
    return ops


def _fn_body(js, name):
    """Body of `async function <name>(...)` by brace matching."""
    start = js.index(f"async function {name}")
    i = js.index("{", start)
    depth = 0
    for j in range(i, len(js)):
        if js[j] == "{":
            depth += 1
        elif js[j] == "}":
            depth -= 1
            if depth == 0:
                return js[i:j + 1]
    raise AssertionError(f"unbalanced braces in {name}")


def _fields_read(body, var):
    """Every `<var>.<field>` the view dereferences (incl. optional
    chaining), minus JS builtins that aren't payload fields."""
    builtins = {"map", "filter", "length", "toFixed", "includes", "push",
                "join", "forEach", "entries", "keys", "slice", "sort"}
    return {f for f in re.findall(rf"\b{var}(?:\?)?\.(\w+)", body)
            if f not in builtins}


def test_api_client_operations_match_openapi():
    """The generated client and the spec cannot drift: one client op per
    spec operation, with the same method + path template."""
    ops = _parse_api_client()
    with open(os.path.join(REPO_ROOT, "proto", "openapi.json")) as f:
        spec = json.load(f)
    spec_ops = {(m.upper(), p)
                for p, methods in spec["paths"].items() for m in methods}
    client_ops = set(ops.values())
    assert client_ops == spec_ops, (
        f"client-only: {sorted(client_ops - spec_ops)}; "
        f"spec-only: {sorted(spec_ops - client_ops)}")
    # The lifecycle surface shipped (docs/serving.md "Model lifecycle").
    for needed in ("postDeploymentsIdUpdate", "postDeploymentsIdCanary",
                   "getModelsNameVersionsV"):
        assert needed in ops, sorted(ops)


def test_app_js_api_calls_resolve():
    """Every API.<op>( call site in app.js exists in the generated
    client — a renamed/removed operation fails here, not as a runtime
    TypeError in the browser."""
    ops = _parse_api_client()
    calls = set(re.findall(r"\bAPI\.(\w+)\(", _js("app.js")))
    assert calls, "app.js makes no API calls?"
    missing = calls - set(ops)
    assert not missing, f"app.js calls unknown client ops: {sorted(missing)}"


def test_serving_and_model_views_bind_live_payloads(cluster):
    """Execute the Serving / deployment-detail / Models views' data
    bindings against a REAL master: every field the JS reads from each
    response object must exist on the live payload (the view field sets
    are extracted from app.js, so UI↔API drift fails in either
    direction). The fixture deployment carries a model version AND an
    active canary so the new lifecycle bindings are exercised."""
    token = cluster.login()
    # Registry fixtures: model + two versions over committed checkpoints.
    cluster.api("POST", "/api/v1/models",
                {"name": "ui-bind", "metadata": {}, "labels": []},
                token=token)
    for uuid in ("ui-ck-1", "ui-ck-2"):
        cluster.api("POST", "/api/v1/checkpoints",
                    {"uuid": uuid, "state": "COMPLETED"}, token=token)
        cluster.api("POST", "/api/v1/models/ui-bind/versions",
                    {"checkpoint_uuid": uuid}, token=token)
    # A live deployment on version 1 with a canary split on version 2.
    dep_cfg = {
        "name": "ui-dep",
        "entrypoint": "python3 -m tests.fixtures.serving.fake_replica",
        "serving": {"model": "gpt2", "model_version": "ui-bind:1",
                    "replicas": {"min": 1, "max": 2, "target": 1}},
        "resources": {"slots_per_trial": 0},
        "environment": {"DET_FAKE_HEARTBEAT_S": "0.3"},
    }
    dep_id = cluster.api("POST", "/api/v1/deployments",
                         {"config": dep_cfg}, token=token)["id"]
    cluster.api("POST", f"/api/v1/deployments/{dep_id}/canary",
                {"model": "ui-bind", "version": 2, "fraction": 0.25},
                token=token)
    # Wait until both replicas heartbeat so latency/report fields exist.
    deadline = time.time() + 90
    detail = {}
    while time.time() < deadline:
        detail = cluster.api("GET", f"/api/v1/deployments/{dep_id}",
                             token=token)["deployment"]
        fresh = [r for r in detail.get("replicas", [])
                 if r.get("allocation_state") == "RUNNING"
                 and 0 <= (r.get("report_age_s") or -1) < 10]
        if len(fresh) == 2:
            break
        time.sleep(0.3)
    assert len(detail.get("replicas", [])) == 2, detail

    js = _js("app.js")

    # pageServing: deployments table binds `d.*`, tasks table binds `t.*`.
    serving_body = _fn_body(js, "pageServing")
    deployments = cluster.api("GET", "/api/v1/deployments",
                              token=token)["deployments"]
    assert deployments
    d = deployments[0]
    for field in _fields_read(serving_body, "d"):
        assert field in d, f"pageServing reads d.{field}; payload: {sorted(d)}"
    # The lifecycle columns really render from the payload.
    assert d["model_version"] == "ui-bind:1"
    assert d["canary"]["version"] == "ui-bind:2"
    serving_tasks = cluster.api("GET", "/api/v1/serving",
                                token=token)["serving"]
    assert serving_tasks
    t0 = serving_tasks[0]
    for field in _fields_read(serving_body, "t"):
        assert field in t0, (
            f"pageServing reads t.{field}; payload: {sorted(t0)}")

    # pageDeployment: header + latency tables bind `d.*`, replica rows
    # bind `r.*`, slow-request rows bind `s.*`. `swap` only exists while
    # a rollout is in flight.
    detail_body = _fn_body(js, "pageDeployment")
    optional = {"swap"}
    for field in _fields_read(detail_body, "d") - optional:
        assert field in detail, (
            f"pageDeployment reads d.{field}; payload: {sorted(detail)}")
    r0 = detail["replicas"][0]
    for field in _fields_read(detail_body, "r"):
        assert field in r0, (
            f"pageDeployment reads r.{field}; payload: {sorted(r0)}")
    assert {"ui-bind:1", "ui-bind:2"} == {
        r["model_version"] for r in detail["replicas"]}

    # pageModels: model rows bind `m.*`, version rows bind `v.*`.
    models_body = _fn_body(js, "pageModels")
    models = cluster.api("GET", "/api/v1/models", token=token)["models"]
    m0 = next(m for m in models if m["name"] == "ui-bind")
    for field in _fields_read(models_body, "m"):
        assert field in m0, (
            f"pageModels reads m.{field}; payload: {sorted(m0)}")
    versions = cluster.api("GET", "/api/v1/models/ui-bind/versions",
                           token=token)["model_versions"]
    v0 = versions[0]
    for field in _fields_read(models_body, "v"):
        assert field in v0, (
            f"pageModels reads v.{field}; payload: {sorted(v0)}")

    cluster.api("POST", f"/api/v1/deployments/{dep_id}/kill", token=token)
