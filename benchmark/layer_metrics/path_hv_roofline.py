"""The Hessian-vector products' share of their HBM roofline: the window's
products (the program's count, ``path_hv_products``' source) times the bytes
ONE read of X and the vectors take (``benchmark/roofline_hv.py``; the operand's
shape is the placed batch's, handed over by the driver), over the peak, against
the device seconds under ``tron/hv`` (``benchmark/path_scopes.py``). Nothing
where either is missing or no product was counted."""
from benchmark import path_scopes
from benchmark.roofline_hv import hv_roofline_pct


def read(ctx):
    counters = ctx["counters"]
    products = sum(n for start, n in counters.get("hv_products", ())
                   if start >= ctx["window_start"])
    part = path_scopes.of_this_run()
    if not products or part is None or "hv_operand" not in counters:
        return None
    return hv_roofline_pct(products, *counters["hv_operand"],
                           part["seconds"]["hv"], ctx["device"]["kind"])
