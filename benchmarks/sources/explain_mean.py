"""Mean over the window's queries of a field of ``r.explain["pipeline"]``,
optionally divided by the table's rows."""


def read(ctx, args):
    values = [r.pipeline[args["field"]] for r in ctx.records
              if r.pipeline and args["field"] in r.pipeline]
    if not values:
        return None
    mean = sum(values) / len(values)
    return mean / ctx.table_rows if args.get("per_table_row") else mean
