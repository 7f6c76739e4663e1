"""Helm chart validation (deploy/charts/) via the helm_lite renderer —
the `helm template | kubectl apply --dry-run` equivalent for an image with
no helm binary.  Parity bar: /root/reference/charts/karpenter-core/templates/
(ServiceMonitor, logging ConfigMap, PDB, SA, RBAC, Deployment, Service) and
charts/karpenter-core-crd/."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from helm_lite import render_chart  # noqa: E402

REPO = os.path.join(os.path.dirname(__file__), "..")
CHART = os.path.join(REPO, "deploy", "charts", "karpenter-core-tpu")
CRD_CHART = os.path.join(REPO, "deploy", "charts", "karpenter-core-tpu-crd")


def flat(docs_by_template):
    return [d for docs in docs_by_template.values() for d in docs]


class TestMainChart:
    def test_renders_with_default_values(self):
        docs = flat(render_chart(CHART))
        kinds = sorted(d["kind"] for d in docs)
        assert kinds == [
            "ClusterRole", "ClusterRoleBinding", "ConfigMap", "ConfigMap",
            "Deployment", "Deployment", "PodDisruptionBudget",
            "PodDisruptionBudget", "Role", "RoleBinding", "Service",
            "Service", "ServiceAccount",
        ]
        for doc in docs:
            assert doc["metadata"]["name"], doc

    def test_servicemonitor_gated_and_well_formed(self):
        # off by default (values.serviceMonitor.enabled: false), real object
        # when enabled — the reference gates it the same way
        # (servicemonitor.yaml:1)
        assert render_chart(CHART)["servicemonitor.yaml"] == []
        docs = render_chart(
            CHART,
            value_overrides={"serviceMonitor": {"enabled": True,
                                                "additionalLabels": {"team": "infra"}}},
        )["servicemonitor.yaml"]
        assert len(docs) == 1
        sm = docs[0]
        assert sm["kind"] == "ServiceMonitor"
        assert sm["apiVersion"] == "monitoring.coreos.com/v1"
        assert sm["metadata"]["labels"]["team"] == "infra"
        endpoint = sm["spec"]["endpoints"][0]
        assert endpoint == {"port": "http-metrics", "path": "/metrics"}
        # the scrape selector must match the metrics Service's labels
        service = render_chart(CHART)["service.yaml"][0]
        sel = sm["spec"]["selector"]["matchLabels"]
        assert all(service["metadata"]["labels"].get(k) == v for k, v in sel.items())

    def test_controller_wiring(self):
        deploy = render_chart(CHART)["deployment.yaml"][0]
        assert deploy["spec"]["replicas"] == 2
        container = deploy["spec"]["template"]["spec"]["containers"][0]
        env = {e["name"]: e.get("value") for e in container["env"]}
        solver_addr = "karpenter-core-tpu-solver.karpenter.svc.cluster.local:8980"
        assert env["KC_SOLVER_ADDRESS"] == solver_addr
        assert env["KC_LEASE_ENDPOINT"] == solver_addr
        assert env["LEADER_ELECT"] == "true"
        ports = {p["name"]: p["containerPort"] for p in container["ports"]}
        assert ports == {"http-metrics": 8080, "http": 8081}
        # kube backend defaults to the hermetic in-memory store; apiserver
        # mode adds the endpoint env (docs/KUBEAPI.md)
        assert env["KC_KUBE_BACKEND"] == "memory"
        assert "KC_KUBE_APISERVER" not in env
        api = render_chart(
            CHART,
            value_overrides={"controller": {"kubeBackend": "apiserver",
                                            "kubeApiserver": "http://127.0.0.1:8001"}},
        )["deployment.yaml"][0]
        env_api = {
            e["name"]: e.get("value")
            for e in api["spec"]["template"]["spec"]["containers"][0]["env"]
        }
        assert env_api["KC_KUBE_BACKEND"] == "apiserver"
        assert env_api["KC_KUBE_APISERVER"] == "http://127.0.0.1:8001"

    def test_solver_pins_jax_platform(self):
        # the deployed TPU solver names its platform so JAX fails at
        # start-up on a node without the accelerator instead of serving
        # from CPU
        solver = render_chart(CHART)["solver.yaml"]
        deploy = next(d for d in solver if d["kind"] == "Deployment")
        env = {
            e["name"]: e.get("value")
            for e in deploy["spec"]["template"]["spec"]["containers"][0]["env"]
        }
        assert env["JAX_PLATFORMS"] == "tpu"

    def test_solver_hostpath_default_and_pvc_option(self):
        solver = render_chart(CHART)["solver.yaml"]
        deploy = next(d for d in solver if d["kind"] == "Deployment")
        volume = deploy["spec"]["template"]["spec"]["volumes"][0]
        assert "hostPath" in volume
        assert not any(d["kind"] == "PersistentVolumeClaim" for d in solver)
        # persistence.enabled switches the lease/compile volume to a PVC
        # (ADVICE r4 #2: survives solver reschedules across nodes)
        solver_pvc = render_chart(
            CHART, value_overrides={"solver": {"persistence": {"enabled": True}}}
        )["solver.yaml"]
        deploy = next(d for d in solver_pvc if d["kind"] == "Deployment")
        volume = deploy["spec"]["template"]["spec"]["volumes"][0]
        assert volume["persistentVolumeClaim"]["claimName"] == (
            "karpenter-core-tpu-solver-cache"
        )
        pvc = next(d for d in solver_pvc if d["kind"] == "PersistentVolumeClaim")
        assert pvc["spec"]["resources"]["requests"]["storage"] == "10Gi"

    def test_solver_requests_the_tpu_resource(self):
        # without the extended-resource request the pod gets no chip and no
        # auto-toleration for the TPU taint — the whole point of the solver
        solver = render_chart(CHART)["solver.yaml"]
        deploy = next(d for d in solver if d["kind"] == "Deployment")
        resources = deploy["spec"]["template"]["spec"]["containers"][0]["resources"]
        assert resources["requests"]["google.com/tpu"] == "1"
        assert resources["limits"]["google.com/tpu"] == "1"
        assert resources["requests"]["cpu"] == "2"

    def test_solver_has_disruption_budget(self):
        # a solver outage halts lease renewal (ADVICE r4 #2): the singleton
        # must BLOCK voluntary evictions — maxUnavailable 1 on replicas 1
        # would permit every eviction and protect nothing
        solver = render_chart(CHART)["solver.yaml"]
        pdb = next(d for d in solver if d["kind"] == "PodDisruptionBudget")
        assert pdb["spec"] == {
            "minAvailable": 1,
            "selector": {
                "matchLabels": {
                    "app.kubernetes.io/name": "karpenter-core-tpu-solver"
                }
            },
        }

    def test_additional_labels_render_everywhere(self):
        docs = render_chart(CHART, value_overrides={"additionalLabels": {"team": "x"}})
        for tmpl in ("deployment.yaml", "service.yaml", "configmap.yaml"):
            for doc in docs[tmpl]:
                assert doc["metadata"]["labels"]["team"] == "x", (tmpl, doc)

    def test_logging_configmap(self):
        docs = render_chart(CHART)["configmap-logging.yaml"]
        assert docs[0]["metadata"]["name"] == "config-logging"
        assert docs[0]["data"]["loglevel.controller"] == "info"

    def test_name_overrides(self):
        docs = render_chart(CHART, value_overrides={"fullnameOverride": "karpenter"})
        assert docs["deployment.yaml"][0]["metadata"]["name"] == "karpenter"
        env = {
            e["name"]: e.get("value")
            for e in docs["deployment.yaml"][0]["spec"]["template"]["spec"][
                "containers"
            ][0]["env"]
        }
        assert env["KC_SOLVER_ADDRESS"].startswith("karpenter-solver.")


class TestCRDChart:
    def test_crds_render_and_match_api_model(self):
        docs = flat(render_chart(CRD_CHART))
        by_name = {d["metadata"]["name"]: d for d in docs}
        assert set(by_name) == {"provisioners.karpenter.sh", "machines.karpenter.sh"}
        prov = by_name["provisioners.karpenter.sh"]
        assert prov["spec"]["scope"] == "Cluster"
        version = prov["spec"]["versions"][0]
        assert version["name"] == "v1alpha5"
        spec_props = version["schema"]["openAPIV3Schema"]["properties"]["spec"][
            "properties"
        ]
        # every ProvisionerSpec field (apis/v1alpha5.py:66-82) is in the schema
        assert set(spec_props) >= {
            "annotations", "labels", "taints", "startupTaints", "requirements",
            "kubeletConfiguration", "provider", "providerRef",
            "ttlSecondsAfterEmpty", "ttlSecondsUntilExpired", "consolidation",
            "weight", "limits",
        }
        machine = by_name["machines.karpenter.sh"]
        machine_props = machine["spec"]["versions"][0]["schema"][
            "openAPIV3Schema"
        ]["properties"]["spec"]["properties"]
        assert set(machine_props) >= {
            "taints", "startupTaints", "requirements", "kubelet", "resources",
            "machineTemplateRef",
        }
