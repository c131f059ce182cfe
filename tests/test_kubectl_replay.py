"""Real-cluster drift guard: the reconciler driven through the REAL
``KubectlClient`` against a scripted ``kubectl`` binary.

``FakeKubeApi`` (test_reconciler*.py) exercises convergence logic but
cannot catch drift in the kubectl CONTRACT itself — wrong flags, wrong
error-string matching, wrong JSON shapes would only surface on a live
cluster (reference counterpart ran against real k8s:
cluster-manager/.../k8s/SeldonDeploymentControllerImpl.java:69-111).
This suite pins that contract without a cluster:

  * a fake ``kubectl`` executable emulates apiserver semantics at the CLI
    boundary — ``Error from server (NotFound)``/``(AlreadyExists)``
    stderr + exit 1, server-side-apply deep-merge, Service clusterIP
    immutability, ``--subresource=status`` isolation — and RECORDS every
    invocation (argv + stdin) to a transcript;
  * the real ``KubectlClient`` + ``Reconciler`` run a full lifecycle
    (CRD bootstrap, CR create -> resource creates, steady state, spec
    bump -> apply, CR delete -> prune);
  * assertions check both the cluster end-state AND the transcript:
    exact flag sets for each verb, and ZERO writes in the steady-state
    tick.
"""

import json
import os
import stat

import pytest

from seldon_core_tpu.operator.reconciler import (
    CRD_NAME,
    KubectlClient,
    Reconciler,
)

FAKE_KUBECTL = r'''#!/usr/bin/env -S python3 -S
"""Scripted kubectl: apiserver semantics at the CLI boundary.

(-S in the shebang: no site-packages scan per kubectl invocation.)"""
import json, os, sys

STATE = os.environ["FAKE_KUBE_STATE"]
TRANSCRIPT = os.environ["FAKE_KUBE_TRANSCRIPT"]
CLUSTER_SCOPED = {"CustomResourceDefinition"}


def load():
    if os.path.exists(STATE):
        with open(STATE) as f:
            return json.load(f)
    return {}


def save(state):
    with open(STATE, "w") as f:
        json.dump(state, f)


def record(argv, stdin):
    with open(TRANSCRIPT, "a") as f:
        f.write(json.dumps({"argv": argv, "stdin": stdin}) + "\n")


def key(kind, ns, name):
    if kind in CLUSTER_SCOPED:
        ns = "default"
    return f"{kind}/{ns}/{name}"


def arg_after(argv, flag, default=None):
    return argv[argv.index(flag) + 1] if flag in argv else default


def fail(msg):
    sys.stderr.write(msg + "\n")
    sys.exit(1)


def canonical_kind(k):
    # kubectl accepts kinds case-insensitively / plurals; the client
    # passes exact Kind strings, so keep it strict but map them through
    return k


def deep_merge(live, incoming):
    if not isinstance(live, dict) or not isinstance(incoming, dict):
        return incoming
    out = dict(live)
    for k, v in incoming.items():
        out[k] = deep_merge(live.get(k), v)
    return out


def main():
    argv = sys.argv[1:]
    stdin = sys.stdin.read() if "-" in argv else ""
    record(argv, stdin)
    state = load()
    verb = argv[0]
    ns = arg_after(argv, "-n", "default")

    if verb == "get":
        kind = canonical_kind(argv[1])
        if len(argv) > 2 and not argv[2].startswith("-"):  # single object
            name = argv[2]
            obj = state.get(key(kind, ns, name))
            if obj is None:
                fail(f'Error from server (NotFound): '
                     f'{kind.lower()}s "{name}" not found')
            print(json.dumps(obj))
            return
        sel = arg_after(argv, "-l")
        items = []
        for k, obj in state.items():
            okind, ons, _ = k.split("/", 2)
            if okind != kind or (kind not in CLUSTER_SCOPED and ons != ns):
                continue
            if sel:
                labels = obj.get("metadata", {}).get("labels", {})
                want = dict(p.split("=", 1) for p in sel.split(","))
                if any(labels.get(a) != b for a, b in want.items()):
                    continue
            items.append(obj)
        print(json.dumps({"kind": "List", "items": items}))
        return

    if verb == "create":
        obj = json.loads(stdin)
        kind = obj["kind"]
        name = obj["metadata"]["name"]
        ons = obj["metadata"].get("namespace", ns)
        k = key(kind, ons, name)
        if k in state:
            fail(f'Error from server (AlreadyExists): '
                 f'{kind.lower()}s "{name}" already exists')
        obj.setdefault("metadata", {})["resourceVersion"] = "1"
        if kind == "Service":
            obj.setdefault("spec", {}).setdefault("clusterIP", "10.0.0.1")
        state[k] = obj
        save(state)
        print(f"{kind.lower()}/{name} created")
        return

    if verb == "apply":
        if "--server-side" not in argv:
            fail("error: this scripted kubectl only accepts "
                 "--server-side apply")
        obj = json.loads(stdin)
        kind = obj["kind"]
        name = obj["metadata"]["name"]
        ons = obj["metadata"].get("namespace", ns)
        k = key(kind, ons, name)
        live = state.get(k)
        if live is not None:
            live_ip = live.get("spec", {}).get("clusterIP")
            new_ip = obj.get("spec", {}).get("clusterIP")
            if (kind == "Service" and live_ip and new_ip
                    and new_ip != live_ip):
                fail('The Service "%s" is invalid: spec.clusterIP: '
                     'Invalid value: field is immutable' % name)
            merged = deep_merge(live, obj)
            merged["metadata"]["resourceVersion"] = str(
                int(live["metadata"].get("resourceVersion", "1")) + 1)
            state[k] = merged
        else:
            obj.setdefault("metadata", {})["resourceVersion"] = "1"
            state[k] = obj
        save(state)
        print(f"{kind.lower()}/{name} serverside-applied")
        return

    if verb == "delete":
        kind = canonical_kind(argv[1])
        name = argv[2]
        k = key(kind, ns, name)
        if k not in state:
            fail(f'Error from server (NotFound): '
                 f'{kind.lower()}s "{name}" not found')
        del state[k]
        save(state)
        print(f"{kind.lower()}/{name} deleted")
        return

    if verb == "patch":
        kind = canonical_kind(argv[1])
        name = argv[2]
        if "--subresource=status" not in argv:
            fail("error: only status subresource patches are scripted")
        patch = json.loads(arg_after(argv, "-p"))
        if set(patch) != {"status"}:
            fail("error: status patch must touch only .status")
        k = key(kind, ns, name)
        obj = state.get(k)
        if obj is None:
            fail(f'Error from server (NotFound): '
                 f'{kind.lower()}s "{name}" not found')
        obj["status"] = deep_merge(obj.get("status", {}), patch["status"])
        obj["metadata"]["resourceVersion"] = str(
            int(obj["metadata"].get("resourceVersion", "1")) + 1)
        save(state)
        print(f"{kind.lower()}/{name} patched")
        return

    fail(f"error: unscripted verb {verb}")


main()
'''

CR = {
    "apiVersion": "machinelearning.seldon.io/v1alpha2",
    "kind": "SeldonDeployment",
    "metadata": {"name": "replay", "namespace": "default",
                 "resourceVersion": "1"},
    "spec": {
        "name": "replay",
        "predictors": [{
            "name": "main",
            "replicas": 1,
            "graph": {"name": "stub", "implementation": "SIMPLE_MODEL",
                      "type": "MODEL"},
        }],
    },
}


@pytest.fixture()
def cluster(tmp_path, monkeypatch):
    kubectl = tmp_path / "kubectl"
    kubectl.write_text(FAKE_KUBECTL)
    kubectl.chmod(kubectl.stat().st_mode | stat.S_IEXEC)
    state = tmp_path / "state.json"
    transcript = tmp_path / "transcript.jsonl"
    monkeypatch.setenv("FAKE_KUBE_STATE", str(state))
    monkeypatch.setenv("FAKE_KUBE_TRANSCRIPT", str(transcript))
    client = KubectlClient(kubectl=str(kubectl))
    return client, state, transcript


def read_transcript(transcript):
    if not os.path.exists(transcript):
        return []
    with open(transcript) as f:
        return [json.loads(line) for line in f if line.strip()]


def seed_cr(state, cr):
    doc = json.loads(state.read_text()) if state.exists() else {}
    doc[f"SeldonDeployment/default/{cr['metadata']['name']}"] = cr
    state.write_text(json.dumps(doc))


def test_full_lifecycle_transcript(cluster):
    client, state, transcript = cluster
    rec = Reconciler(client, namespace="default")

    # --- CRD bootstrap -----------------------------------------------------
    assert rec.ensure_crd() is True
    assert rec.ensure_crd() is False  # idempotent second boot
    tr = read_transcript(transcript)
    creates = [t for t in tr if t["argv"][0] == "create"]
    assert len(creates) == 1 and json.loads(
        creates[0]["stdin"])["metadata"]["name"] == CRD_NAME

    # --- CR appears: resources created ------------------------------------
    seed_cr(state, CR)
    results = rec.run_once()
    assert results["replay"]["creates"] >= 2  # Deployment + Service
    live = json.loads(state.read_text())
    kinds = sorted(k.split("/", 1)[0] for k in live)
    assert "Deployment" in kinds and "Service" in kinds
    # status written back through the REAL --subresource=status flag
    cr_live = live["SeldonDeployment/default/replay"]
    assert cr_live.get("status", {}).get("state")

    # --- steady state: ZERO writes -----------------------------------------
    before = len(read_transcript(transcript))
    results = rec.run_once()
    assert results["replay"] == {"creates": 0, "updates": 0, "deletes": 0}
    steady = read_transcript(transcript)[before:]
    write_verbs = [t["argv"][0] for t in steady
                   if t["argv"][0] in ("create", "apply", "delete")]
    assert write_verbs == [], f"steady state wrote: {write_verbs}"

    # --- spec change: server-side apply with the exact flag set ------------
    bumped = json.loads(json.dumps(CR))
    bumped["spec"]["predictors"][0]["replicas"] = 3
    seed_cr(state, bumped)
    before = len(read_transcript(transcript))
    results = rec.run_once()
    assert results["replay"]["updates"] >= 1
    applies = [t for t in read_transcript(transcript)[before:]
               if t["argv"][0] == "apply"]
    assert applies, "spec change produced no apply"
    for t in applies:
        assert "--server-side" in t["argv"]
        assert "--force-conflicts" in t["argv"]
    # the merged Deployment really carries the new replica count
    live = json.loads(state.read_text())
    deps = [v for k, v in live.items() if k.startswith("Deployment/")]
    assert any(d["spec"]["replicas"] == 3 for d in deps)

    # --- CR deleted: owned resources pruned --------------------------------
    doc = json.loads(state.read_text())
    del doc["SeldonDeployment/default/replay"]
    state.write_text(json.dumps(doc))
    results = rec.run_once()
    assert results["replay"]["deletes"] >= 2
    live = json.loads(state.read_text())
    assert not any(k.startswith(("Deployment/", "Service/")) for k in live)


def test_service_clusterip_immutability_respected(cluster):
    """A re-rendered Service (no clusterIP) must APPLY cleanly onto a live
    Service that has one — the exact failure a bare ``kubectl replace``
    hits on a real cluster (the reason KubectlClient uses server-side
    apply)."""
    client, state, transcript = cluster
    rec = Reconciler(client, namespace="default")
    rec.ensure_crd()
    seed_cr(state, CR)
    rec.run_once()
    # force a respec so every owned resource re-applies
    bumped = json.loads(json.dumps(CR))
    bumped["spec"]["predictors"][0]["annotations"] = {"rev": "2"}
    seed_cr(state, bumped)
    results = rec.run_once()
    assert results["replay"].get("failed", 0) == 0
    live = json.loads(state.read_text())
    svcs = [v for k, v in live.items() if k.startswith("Service/")]
    assert svcs and all(
        s["spec"].get("clusterIP") == "10.0.0.1" for s in svcs
    ), "server-side apply must preserve the live clusterIP"


def test_error_string_contract(cluster):
    """KubectlClient's stderr-string matching against the scripted
    apiserver wording: NotFound -> None/KeyError, AlreadyExists ->
    KeyError, unknown -> RuntimeError."""
    client, state, transcript = cluster
    assert client.get("Deployment", "default", "nope") is None
    with pytest.raises(KeyError):
        client.delete("Deployment", "default", "nope")
    client.create({"kind": "Deployment", "apiVersion": "apps/v1",
                   "metadata": {"name": "x", "namespace": "default"}})
    with pytest.raises(KeyError):
        client.create({"kind": "Deployment", "apiVersion": "apps/v1",
                       "metadata": {"name": "x", "namespace": "default"}})
