"""agg: a masked aggregate, ``FeatureService.agg_where(predicate, column,
aggregate)``, with the predicate, column and aggregate the configuration's
loader makes of the query and its parameters. The answer is one number,
compared with the configuration's reference ``compare_answers``."""


def ask(dep, query: str, params: dict) -> float:
    pred, column, agg = dep.program.query(query, params)
    return dep.service.agg_where(pred, column, agg)


def compare(ref, raw, answers: list) -> dict:
    return ref.compare_answers(raw, answers)


def control(ref, raw, params: list) -> dict:
    return ref.compare_answers(raw, [(p, None) for p in params],
                               control=True)
