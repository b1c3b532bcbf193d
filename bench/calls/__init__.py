"""One module per facade call a traffic mix can name.  Each gives
``invoke(repro, graph, kwargs)`` -> the answer as host numpy data,
``reference(indptr, indices, kwargs)`` -> the plain reference's answer,
``control(indptr, indices, kwargs)`` -> the reference with one stated
guarantee broken, and ``compare(answer, ref, ctx)`` -> the numbers
compared, each held to ``LIMITS``."""
