"""ModelServer controller: ModelServer CR → Deployment + Service + route.

Closes the serving loop the reference only documents: its TF-Serving
component (removed; `/root/reference/docs_dev/tf_serving.md:1-60`,
smoke-tested by `/root/reference/testing/test_tf_serving.py`) was a
Deployment behind the same Service/VirtualService machinery as
notebooks. TPU-native restatement:

- the pod runs `python -m kubeflow_tpu.serving` (the engine CLI) with
  flags rendered from the spec — continuous batching + AOT warmup on
  by default, so Ready means "compiled, no first-request stall";
- checkpoint source dispatch mirrors the tensorboard controller's
  logspath dispatch (`tensorboard_controller.go:170-239` pattern):
  `pvc://name/subpath` mounts the PVC at /ckpt, `gs://` mounts the
  user-gcp-sa secret, "" runs --random (smoke/dev);
- TPU placement rides the SAME machinery as notebooks: topology label
  for the webhook's env injection, slice-pool node selector, chip
  resources (`controllers/notebook.py` wiring);
- route prefix `/serving/<ns>/<name>/` → the pod's REST port, and
  status.url surfaces it (`notebook_controller.go:483-510` pattern).
"""

from __future__ import annotations

import os
import time

from kubeflow_tpu.api.core import (
    Container,
    Deployment,
    DeploymentSpec,
    EnvVar,
    HTTPRoute,
    PodTemplateSpec,
    Probe,
    Service,
    ServicePort,
    ServiceSpec,
    VirtualService,
    VirtualServiceSpec,
    Volume,
    VolumeMount,
)
from kubeflow_tpu.api.crds import ModelServer
from kubeflow_tpu.controlplane.controllers.helpers import (
    copy_spec_and_labels,
    reconcile_child,
)
from kubeflow_tpu.controlplane.controllers.notebook import (
    TOPOLOGY_NODE_SELECTOR,
    TPU_RESOURCE_KEY,
)
from kubeflow_tpu.controlplane.runtime import Controller, Result
from kubeflow_tpu.controlplane.store import NotFound, Store
from kubeflow_tpu.controlplane import webhook as wh
from kubeflow_tpu.parallel.mesh import SLICE_TOPOLOGIES

# Mirror of serving.__main__.MODEL_NAMES: importing the serving package
# would pull jax into the control plane (which is deliberately jax-free
# — controllers must never touch a TPU backend). Drift is pinned by
# tests/test_modelserver.py.
MODEL_NAMES = ("llama-tiny", "llama3-1b", "llama3-8b", "gemma-tiny",
               "gemma-2b", "mixtral-tiny", "granite-hybrid-tiny",
               "granite-4.0-h-micro")

DEFAULT_IMAGE = "kubeflow-tpu/serving:latest"  # KFTPU_SERVING_IMAGE env
SERVE_PORT = 8000
MS_NAME_LABEL = "modelserver-name"
# Disaggregated pools render one Deployment per pool; the pool label
# keeps their selectors disjoint (two Deployments selecting the same
# label set would adopt each other's pods in a real cluster).
MS_POOL_LABEL = "modelserver-pool"

# Autoscale handshake (ISSUE 3): whatever consumes the fleet router's
# /fleet/autoscale recommendation writes the number here; the
# controller clamps it into [spec.replicas, spec.max_replicas].
DESIRED_REPLICAS_ANNOTATION = "kubeflow-tpu.dev/desired-replicas"
# Disaggregated twin (ISSUE 12): the consumer of
# /fleet/autoscale?pools=1 writes the per-pool split here; each is
# clamped into [spec.<pool>_replicas, spec.max_replicas].
DESIRED_PREFILL_ANNOTATION = "kubeflow-tpu.dev/desired-prefill-replicas"
DESIRED_DECODE_ANNOTATION = "kubeflow-tpu.dev/desired-decode-replicas"
# Scale-down protocol: excess pods are annotated draining-since first
# (a real deployment POSTs /fleet/drain, which now pushes every
# in-flight sequence to healthy peers via live KV-block migration);
# only after DRAIN_GRACE_S does the controller delete them and shrink
# the Deployment. With migrate-and-exit the replica is empty within
# ~2 s regardless of generation length, so the grace window matches
# that bound instead of the old wait-out-the-longest-generation guess.
# Module constant so tests shrink the window instead of sleeping.
DRAIN_ANNOTATION = "kubeflow-tpu.dev/draining-since"
DRAIN_GRACE_S = 2.0
# Rollout handshake (ISSUE 18): whatever consumes the fleet router's
# /fleet/versions registry (the promoted `current` version) writes it
# here; the rendered pods boot with `--model-version <value>` so a
# restarted replica re-registers under the promoted label instead of
# the stale spec default. Annotation wins over spec.model_version.
MODEL_VERSION_ANNOTATION = "kubeflow-tpu.dev/model-version"


class ModelServerController(Controller):
    KIND = "ModelServer"
    OWNS = ("Deployment", "Service", "VirtualService")

    def __init__(self, *, use_routing: bool = True):
        self.use_routing = use_routing

    def reconcile(self, store: Store, namespace: str, name: str) -> Result:
        try:
            ms = store.get("ModelServer", namespace, name)
        except NotFound:
            return Result()
        assert isinstance(ms, ModelServer)

        # user-config errors surface as events, not retry loops (the
        # notebook controller's InvalidTopology discipline)
        problem = self._validate(ms)
        if problem:
            reason, msg = problem
            if not any(e.reason == reason for e in
                       store.events_for("ModelServer", namespace, name)):
                store.emit_event(ms, "Warning", reason, msg)
            return Result()

        disagg = ms.spec.prefill_replicas > 0
        requeue = None
        if disagg:
            # one Deployment per pool; each scale-down drains through
            # the same window as the symmetric path
            for suffix, pool, want in (
                    ("-prefill", "prefill",
                     self._desired_pool_count(store, ms, "prefill")),
                    ("-decode", "decode",
                     self._desired_pool_count(store, ms, "decode"))):
                child = name + suffix
                cur_dep = store.try_get("Deployment", namespace, child)
                if cur_dep is not None and want < cur_dep.spec.replicas:
                    want, rq = self._drain_scale_down(
                        store, ms, cur_dep, want)
                    if rq is not None:
                        requeue = rq if requeue is None \
                            else min(requeue, rq)
                dep = self._desired_deployment(
                    ms, replicas=want, pool=pool, child_name=child)
                reconcile_child(store, ms, dep, copy_spec_and_labels)
            # a spec flipped from symmetric: retire the old fleet
            try:
                store.delete("Deployment", namespace, name)
            except NotFound:
                pass
        else:
            desired = self._desired_replica_count(store, ms)
            cur_dep = store.try_get("Deployment", namespace, name)
            if cur_dep is not None and desired < cur_dep.spec.replicas:
                # scale-down drains before delete: hold the Deployment
                # at its current size while excess pods sit in their
                # drain window, then delete them and shrink
                desired, requeue = self._drain_scale_down(
                    store, ms, cur_dep, desired)
            dep = self._desired_deployment(ms, replicas=desired)
            reconcile_child(store, ms, dep, copy_spec_and_labels)
            for suffix in ("-prefill", "-decode"):
                # a spec flipped from disaggregated: retire the pools
                try:
                    store.delete("Deployment", namespace, name + suffix)
                except NotFound:
                    pass
        svc = self._desired_service(ms)
        reconcile_child(store, ms, svc, copy_spec_and_labels)
        if self.use_routing:
            vs = self._desired_virtualservice(ms)
            reconcile_child(store, ms, vs, copy_spec_and_labels)

        if disagg:
            deps = [store.try_get("Deployment", namespace,
                                  name + suffix)
                    for suffix in ("-prefill", "-decode")]
            ready = all(d is not None and d.ready_replicas >= 1
                        for d in deps)
            conditions = [c for d in deps if d
                          for c in d.conditions]
        else:
            cur = store.try_get("Deployment", namespace, name)
            ready = bool(cur and cur.ready_replicas >= 1)
            conditions = list(cur.conditions) if cur else []
        url = f"/serving/{namespace}/{name}/" if self.use_routing else \
            f"http://{name}.{namespace}.svc"
        fresh = store.try_get("ModelServer", namespace, name)
        if fresh is not None and (
                fresh.status.ready != ready
                or fresh.status.conditions != conditions
                or fresh.status.url != url):
            fresh.status.ready = ready
            fresh.status.conditions = conditions
            fresh.status.url = url
            store.update(fresh)
        return Result(requeue_after=requeue)

    def _desired_replica_count(self, store: Store, ms: ModelServer) -> int:
        """spec.replicas, lifted by the autoscale annotation when
        max_replicas enables it — clamped to [replicas, max_replicas]
        so a runaway recommender can never scale past the operator's
        ceiling or below the configured baseline."""
        spec = ms.spec
        desired = max(1, spec.replicas)
        ann = ms.metadata.annotations.get(DESIRED_REPLICAS_ANNOTATION)
        if ann is None or not spec.max_replicas:
            return desired
        try:
            want = int(ann)
        except ValueError:
            reason = "InvalidDesiredReplicas"
            if not any(e.reason == reason for e in store.events_for(
                    "ModelServer", ms.metadata.namespace,
                    ms.metadata.name)):
                store.emit_event(
                    ms, "Warning", reason,
                    f"annotation {DESIRED_REPLICAS_ANNOTATION}={ann!r} "
                    "is not an integer; using spec.replicas")
            return desired
        return max(spec.replicas, min(want, spec.max_replicas))

    def _desired_pool_count(self, store: Store, ms: ModelServer,
                            pool: str) -> int:
        """Per-pool twin of `_desired_replica_count`: the spec's pool
        size, lifted by the pool's autoscale annotation (written off
        `/fleet/autoscale?pools=1`) and clamped into
        [spec.<pool>_replicas, spec.max_replicas]."""
        spec = ms.spec
        floor = max(1, spec.prefill_replicas if pool == "prefill"
                    else spec.decode_replicas)
        ann_key = (DESIRED_PREFILL_ANNOTATION if pool == "prefill"
                   else DESIRED_DECODE_ANNOTATION)
        ann = ms.metadata.annotations.get(ann_key)
        if ann is None or not spec.max_replicas:
            return floor
        try:
            want = int(ann)
        except ValueError:
            reason = "InvalidDesiredReplicas"
            if not any(e.reason == reason for e in store.events_for(
                    "ModelServer", ms.metadata.namespace,
                    ms.metadata.name)):
                store.emit_event(
                    ms, "Warning", reason,
                    f"annotation {ann_key}={ann!r} is not an "
                    f"integer; using spec {pool} size")
            return floor
        return max(floor, min(want, spec.max_replicas))

    @staticmethod
    def _drain_scale_down(store: Store, ms: ModelServer, cur_dep,
                          desired: int):
        """Mark excess pods draining (newest first are removed; the
        oldest `desired` stay), hold the Deployment at its current
        size until every excess pod's drain window has elapsed, then
        delete the drained pods and let the Deployment shrink.
        Returns (replicas_to_render_now, requeue_after)."""
        ns, name = ms.metadata.namespace, ms.metadata.name
        now = time.time()
        pods = sorted(
            store.list("Pod", ns, owner_uid=cur_dep.metadata.uid),
            key=lambda p: (p.metadata.creation_timestamp,
                           p.metadata.name))
        excess = pods[desired:]
        if not excess:
            # pods already gone (or never created): shrink directly
            return desired, None
        remaining = 0.0
        newly = []
        for pod in excess:
            since = pod.metadata.annotations.get(DRAIN_ANNOTATION)
            if since is None:
                pod.metadata.annotations[DRAIN_ANNOTATION] = repr(now)
                store.update(pod)
                newly.append(pod.metadata.name)
                remaining = max(remaining, DRAIN_GRACE_S)
            else:
                remaining = max(
                    remaining, float(since) + DRAIN_GRACE_S - now)
        if newly:
            store.emit_event(
                ms, "Normal", "DrainingReplica",
                f"draining {len(newly)} replica pod(s) before "
                f"scale-down to {desired}")
        if remaining > 0:
            # hold at current size; requeue when the window closes
            return cur_dep.spec.replicas, remaining
        for pod in excess:
            try:
                store.delete("Pod", ns, pod.metadata.name)
            except NotFound:
                pass
        store.emit_event(ms, "Normal", "ScaledDown",
                         f"scaled {name} to {desired} replica(s) after "
                         "drain")
        return desired, None

    @staticmethod
    def _validate(ms: ModelServer):
        spec = ms.spec
        if spec.model not in MODEL_NAMES:
            return ("InvalidModel",
                    f"unknown model {spec.model!r}; known: "
                    f"{sorted(MODEL_NAMES)}")
        if spec.tpu.topology and spec.tpu.topology not in SLICE_TOPOLOGIES:
            return ("InvalidTopology",
                    f"unknown TPU slice topology {spec.tpu.topology!r}; "
                    f"known: {sorted(SLICE_TOPOLOGIES)}")
        if spec.quant not in ("", "int8"):
            return ("InvalidQuant",
                    f"unknown quant mode {spec.quant!r}")
        # non-positive numerics would render a Deployment whose CLI
        # dies at startup — a crash loop instead of this event
        if spec.max_len < 1 or spec.max_batch < 1 \
                or spec.prefill_chunk < 0:
            return ("InvalidSpec",
                    f"max_len ({spec.max_len}) and max_batch "
                    f"({spec.max_batch}) must be >= 1; prefill_chunk "
                    f"({spec.prefill_chunk}) must be >= 0")
        if spec.replicas < 1:
            return ("InvalidReplicas",
                    f"replicas ({spec.replicas}) must be >= 1")
        if spec.max_replicas and spec.max_replicas < spec.replicas:
            return ("InvalidReplicas",
                    f"max_replicas ({spec.max_replicas}) must be 0 "
                    f"(autoscale off) or >= replicas ({spec.replicas})")
        ckpt = spec.checkpoint
        if ckpt and not (ckpt.startswith("pvc://")
                         or ckpt.startswith("gs://")):
            return ("InvalidCheckpoint",
                    f"checkpoint {ckpt!r} must be pvc://name/path, "
                    "gs://bucket/path, or empty (random init)")
        if ckpt.startswith("pvc://") \
                and not ckpt[len("pvc://"):].partition("/")[0]:
            # an empty claim name would render an unbound volume whose
            # failure surfaces as an opaque kubelet error, not an event
            return ("InvalidCheckpoint",
                    f"checkpoint {ckpt!r} names no PVC")
        if ckpt.startswith("gs://") and not ckpt[len("gs://"):]:
            return ("InvalidCheckpoint",
                    f"checkpoint {ckpt!r} names no bucket")
        if spec.warmup and not spec.continuous:
            return ("InvalidWarmup",
                    "warmup requires continuous batching (the window "
                    "batcher has no ahead-of-traffic shape set)")
        if spec.prefill_replicas < 0 or spec.decode_replicas < 0:
            return ("InvalidReplicas",
                    f"prefill_replicas ({spec.prefill_replicas}) and "
                    f"decode_replicas ({spec.decode_replicas}) must "
                    "be >= 0")
        if (spec.prefill_replicas > 0) != (spec.decode_replicas > 0):
            return ("InvalidReplicas",
                    "disaggregation needs BOTH prefill_replicas and "
                    "decode_replicas > 0 (a lone pool cannot serve); "
                    "set both to 0 for a symmetric fleet")
        if spec.prefill_replicas > 0 and not spec.continuous:
            return ("InvalidPool",
                    "disaggregated pools require continuous batching "
                    "(the prefill->decode handoff ships paged KV "
                    "blocks)")
        return None

    def _desired_deployment(self, ms: ModelServer, replicas: int = 1,
                            pool: str = "",
                            child_name: str = "") -> Deployment:
        name, ns = ms.metadata.name, ms.metadata.namespace
        spec = ms.spec
        volumes: list[Volume] = []
        mounts: list[VolumeMount] = []
        env: list[EnvVar] = []

        args = ["--model", spec.model, "--port", str(SERVE_PORT),
                "--max-len", str(spec.max_len),
                "--max-batch", str(spec.max_batch)]
        ckpt = spec.checkpoint
        if ckpt.startswith("pvc://"):
            rest = ckpt[len("pvc://"):]
            pvc_name, _, sub_path = rest.partition("/")
            volumes.append(Volume(name="ckpt", pvc_name=pvc_name))
            mounts.append(VolumeMount(name="ckpt", mount_path="/ckpt",
                                      sub_path=sub_path))
            args += ["--checkpoint", "/ckpt"]
        elif ckpt.startswith("gs://"):
            volumes.append(Volume(name="gcp-creds", secret="user-gcp-sa"))
            mounts.append(VolumeMount(name="gcp-creds",
                                      mount_path="/secret/gcp"))
            env.append(EnvVar("GOOGLE_APPLICATION_CREDENTIALS",
                              "/secret/gcp/user-gcp-sa.json"))
            args += ["--checkpoint", ckpt]
        else:
            args += ["--random"]
        if spec.continuous:
            args += ["--continuous"]
        if spec.warmup:
            args += ["--warmup"]
        if spec.prefill_chunk:
            args += ["--prefill-chunk-tokens", str(spec.prefill_chunk)]
        if spec.quant:
            args += ["--quant", spec.quant]
        # "none"/"" force byte mode; "auto" lets the server pick up
        # tokenizer.json beside the checkpoint (the Checkpointer
        # carries it there from tools/prepare_data.py's output) so a
        # served prepared checkpoint speaks its training tokenizer.
        # ONLY "auto" is gated on a checkpoint being set (it is a
        # no-op without one, and not rendering it then keeps
        # random-init servers runnable on serving images predating the
        # auto mode); an EXPLICIT tokenizer path renders regardless —
        # silently dropping configuration the operator asked for would
        # serve byte-mode text with no error anywhere.
        if spec.tokenizer and spec.tokenizer != "none" \
                and (ckpt or spec.tokenizer != "auto"):
            args += ["--tokenizer", spec.tokenizer]
        if pool:
            args += ["--pool", pool]
        # model-version label (ISSUE 18): the annotation (written by
        # the rollout consumer after a promote) overrides the spec
        # default, so restarted pods re-register under the PROMOTED
        # version instead of resurrecting a stale label
        version = ms.metadata.annotations.get(
            MODEL_VERSION_ANNOTATION, "") or spec.model_version
        if version:
            args += ["--model-version", version]

        container = Container(
            name=child_name or name,
            image=os.environ.get("KFTPU_SERVING_IMAGE", DEFAULT_IMAGE),
            command=["python", "-m", "kubeflow_tpu.serving"],
            args=args,
            env=env,
            ports=[SERVE_PORT],
            volume_mounts=mounts,
            # Ready must mean LISTENING — checkpoint restore + warmup
            # compiles run for minutes before the port binds, and the
            # server only answers /readyz after on_startup (warmup)
            # finishes. Without this probe a real kubelet would mark
            # the pod Ready at process start and the route would serve
            # connection-refused.
            readiness_probe=Probe(path="/readyz", port=SERVE_PORT,
                                  initial_delay_seconds=5,
                                  period_seconds=5),
        )
        selector = {MS_NAME_LABEL: name}
        if pool:
            selector[MS_POOL_LABEL] = pool
        dep = Deployment(
            spec=DeploymentSpec(
                replicas=replicas,
                selector=dict(selector),
                template=PodTemplateSpec(),
            )
        )
        tmpl = dep.spec.template
        tmpl.metadata.labels = dict(selector)
        topo_name = spec.tpu.topology
        if topo_name:
            # same placement + webhook-env path as notebook gangs
            tmpl.metadata.labels[wh.TOPOLOGY_LABEL] = topo_name
            topo = SLICE_TOPOLOGIES[topo_name]
            tmpl.spec.node_selector.setdefault(
                TOPOLOGY_NODE_SELECTOR, topo_name)
            container.resources.limits.setdefault(
                TPU_RESOURCE_KEY, str(topo.chips_per_host))
        tmpl.spec.containers = [container]
        tmpl.spec.volumes = volumes
        dep.metadata.name = child_name or name
        dep.metadata.namespace = ns
        dep.metadata.labels = dict(selector)
        return dep

    def _desired_service(self, ms: ModelServer) -> Service:
        name, ns = ms.metadata.name, ms.metadata.namespace
        svc = Service(
            spec=ServiceSpec(
                selector={MS_NAME_LABEL: name},
                ports=[ServicePort("http", 80, SERVE_PORT)],
            )
        )
        svc.metadata.name = name
        svc.metadata.namespace = ns
        return svc

    def _desired_virtualservice(self, ms: ModelServer) -> VirtualService:
        name, ns = ms.metadata.name, ms.metadata.namespace
        vs = VirtualService(
            spec=VirtualServiceSpec(
                gateways=["kubeflow-gateway"],
                hosts=["*"],
                http=[HTTPRoute(
                    prefix=f"/serving/{ns}/{name}/",
                    rewrite="/",
                    destination_host=f"{name}.{ns}.svc",
                    destination_port=80,
                )],
            )
        )
        vs.metadata.name = f"modelserver-{ns}-{name}"
        vs.metadata.namespace = ns
        return vs
