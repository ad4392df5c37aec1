"""What the routers did over the window, from the program's own record:
one `moe/route` span a train step in `code2vec_tpu.obs.trace
.memory_tracer()` (`code2vec_tpu/obs/route.py`: `layers`, per expert
layer the rows each held expert took; `rows_here`, their sum;
`valid_tokens`). The window's steps are the record's last
`ctx.window["steps"]`: the kind flushes the recorder once the window has
closed.

`args["value"]`:
  imbalance        the fullest held expert's rows over the mean of the
                   held experts', by the worst layer of a step, a mean
                   over the window's steps (1 = even)
  held_row_share   rows routed to held experts over every choice the
                   window's valid tokens made (`num_experts_per_tok` a
                   token and expert layer), in percent

A program that keeps no such record gives None, as does a record shorter
than the window.
"""


def reduce(records: list, steps: int, value: str, per_token: int):
    if not steps or len(records) < steps:
        return None
    attrs = [r["attrs"] for r in records[-steps:]]
    if value == "held_row_share":
        choices = sum(a["valid_tokens"] * per_token * len(a["layers"])
                      for a in attrs)
        return 100.0 * sum(a["rows_here"] for a in attrs) / choices \
            if choices else None
    worst = []
    for a in attrs:
        layers = [rows for rows in a["layers"] if sum(rows)]
        if layers:
            worst.append(max(max(rows) * len(rows) / sum(rows)
                             for rows in layers))
    return sum(worst) / len(worst) if worst else None


def read(ctx, args):
    try:
        from code2vec_tpu.obs.trace import memory_tracer
    except ImportError:     # a program that keeps no record
        return None
    return reduce(memory_tracer().records("moe/route"), ctx.window["steps"],
                  args["value"], ctx.config.get("num_experts_per_tok", 0))
