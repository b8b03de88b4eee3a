"""Version slots the delta anti-entropy rounds shipped (``DeltaSyncStats.
payload_slots``) per divergent key repaired in the window."""


def read(w):
    ae = w.get("ae")
    if not ae or not w.get("repaired_keys"):
        return None
    return ae["payload_slots"] / w["repaired_keys"]
