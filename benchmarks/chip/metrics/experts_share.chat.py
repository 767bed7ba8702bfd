"""Share of the traced slice's device busy time spent in the routed
experts: the ops whose HLO op name (``tf_op``) lies under the program's
``moe_experts`` scope (router, dispatch, the held experts' grouped GEMMs
with their re-quantization, combine; not the shared experts)."""

from chipbench import op_scopes


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    return op_scopes.scope_share(tr, tr["scopes"], "moe_experts")
