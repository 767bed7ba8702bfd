"""Share of the traced slice's device busy time spent re-quantizing
weights: the self time of the device ops whose HLO op name (``tf_op``)
lies under the program's ``weight_quant`` scope (each weight's cast,
amax, scale and int8 codes, every call), over the busy time."""

from chipbench import op_scopes


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    return op_scopes.scope_share(tr, tr["scopes"], "weight_quant")
