"""Node utilities (mirror of /root/reference/pkg/utils/node/node.go:30-60)."""

from __future__ import annotations

from typing import List

from karpenter_core_tpu.apis.objects import Node, Pod
from karpenter_core_tpu.utils import pod as pod_util


def get_node_pods(kube_client, *nodes: Node) -> List[Pod]:
    """Reschedulable pods on the nodes: excludes node-owned, daemonset,
    terminal, and terminating pods."""
    pods: List[Pod] = []
    for name in dict.fromkeys(n.name for n in nodes):
        for pod in kube_client.pods_on_node(name):
            if (
                pod_util.is_owned_by_node(pod)
                or pod_util.is_owned_by_daemon_set(pod)
                or pod_util.is_terminal(pod)
                or pod_util.is_terminating(pod)
            ):
                continue
            pods.append(pod)
    return pods


def get_condition(node: Node, condition_type: str):
    for condition in node.status.conditions:
        if condition.type == condition_type:
            return condition
    return None
