"""Graph layout per call: every host conversion of the fresh
``repro.Graph`` (CSR -> ELL or sliced ELL + spill, degrees, device
copies), from the program's ``graph.conversion_seconds`` histogram."""


def read(ctx):
    found = [s.value for s in ctx.obs if s.name == "graph.conversion_seconds"]
    return sum(found) / ctx.calls * 1e3 if found else None
