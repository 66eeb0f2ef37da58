"""Start one served replica with a fault planted in it, for the tests that
``correct`` catches each fault a cell can have:

    python plant.py <fault> <node_runner arguments...>

  state_unchanged   every write is acknowledged and none changes the store
  half_batch        a coordinator acknowledges a whole client batch but
                    runs only its first half
  no_exchange       fast-path commits are never sent to the other replicas
  altered_answer    read results are changed where the reply is built
"""

import sys

from repro.core import rsm
from repro.core.woc import WocReplica
from repro.transport import net, node_runner


class _FrozenStore(dict):
    def __setitem__(self, key, value):
        pass


def plant(fault: str) -> None:
    if fault == "state_unchanged":
        init = rsm.RSM.__init__

        def frozen_init(self, *a, **kw):
            init(self, *a, **kw)
            self.store = _FrozenStore()
        rsm.RSM.__init__ = frozen_init
    elif fault == "half_batch":
        on_req = WocReplica.on_client_req

        def half(self, msg, now):
            ops = msg.payload["ops"]
            keep = len(ops) // 2
            for op in ops[keep:]:
                self.credit_op(msg.src, msg.payload["batch_id"], op.op_id)
            msg.payload["ops"] = ops[:keep]
            on_req(self, msg, now)
        WocReplica.on_client_req = half
    elif fault == "no_exchange":
        post = net.NetContext.post

        def drop_commits(self, msg):
            if msg.kind == "fast_commit" and msg.dst < self.n:
                return
            post(self, msg)
        net.NetContext.post = drop_commits
    elif fault == "altered_answer":
        enrich = net.NetContext._enrich_reply

        def alter(self, payload):
            enrich(self, payload)
            for k, v in (payload.get("results") or {}).items():
                payload["results"][k] = (v or 0) + 1
        net.NetContext._enrich_reply = alter
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(sys.argv[1])
    node_runner.main(sys.argv[2:])
