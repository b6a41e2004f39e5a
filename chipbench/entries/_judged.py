"""The record every entry's ``judge`` returns: the numbers compared, each
beside its limit.  Exact comparisons: both limits are 0."""


def record(wrong: int, unanswered: int, compared: int) -> dict:
    return {
        "wrong_answers": {"value": wrong, "limit": 0},
        "unanswered_requests": {"value": unanswered, "limit": 0},
        "answers_compared": {"value": compared, "at_least": 1},
    }
