"""hyperkube: every binary in one entry point.

Reference: cmd/hyperkube — one fat binary that dispatches to
kube-apiserver/kube-scheduler/kube-proxy/kubectl/kubelet by its first
argument (or by the name it was invoked as). Here:

    python -m kubernetes_tpu.cli.hyperkube <component> [args...]

with components kubectl, kube-scheduler, kube-proxy, kubeadm,
autopilot (offline weight training + standalone promotion CI), and
csi-mock-driver (the standalone mock CSI driver process).
"""

from __future__ import annotations

import sys
from typing import List, Optional


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)

    def _load(name):
        if name in ("kubectl",):
            from . import kubectl as m
        elif name in ("kube-scheduler", "scheduler"):
            from . import kube_scheduler as m
        elif name in ("kube-proxy", "proxy"):
            from . import kube_proxy as m
        elif name == "kubeadm":
            from . import kubeadm as m
        elif name == "autopilot":
            from . import autopilot as m
        elif name == "csi-mock-driver":
            from ..volume import csi as m
        else:
            return None
        return m

    usage = ("usage: hyperkube <component> [args...]\n"
             "components: kubectl kube-scheduler kube-proxy kubeadm "
             "autopilot csi-mock-driver")
    if argv and argv[0] in ("-h", "--help", "help"):
        print(usage)  # requested help: stdout, success
        return 0
    if not argv:
        print(usage, file=sys.stderr)  # usage error
        return 1
    mod = _load(argv[0])
    if mod is None:
        print(f"error: unknown component {argv[0]!r}", file=sys.stderr)
        return 1
    return mod.main(argv[1:])


if __name__ == "__main__":
    if sys.argv[1:2] in (["kube-scheduler"], ["scheduler"]):
        from ..utils import compile_cache

        compile_cache.enable()
    sys.exit(main())
