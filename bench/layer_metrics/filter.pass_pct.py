"""Share of the fresh candidates the filtered beam scored that passed
their query's predicate, in % (``with_stats`` ``filter_passed`` /
``filter_scored``); None where the program counts neither."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("filter_scored"):
        return None
    return 100.0 * c["filter_passed"] / c["filter_scored"]
