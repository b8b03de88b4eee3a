"""Client ops per scheduler flush over the window: ``OpScheduler``'s
``ops_submitted`` over its ``flushes``, both as window differences."""


def read(w):
    sch = w.get("scheduler")
    if not sch or not sch["flushes"]:
        return None
    return sch["ops_submitted"] / sch["flushes"]
