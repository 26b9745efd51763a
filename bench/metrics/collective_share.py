"""Percent of the devices' busy time spent in collective operations
(``trace.collective_s`` over ``trace.busy_s``, both averaged over the chips
used).  Nothing where no trace was taken or the run used one chip.  On a
mesh a trace without collectives reads 0: a sharded model all-reduces in
every layer, so 0 says that the reducer missed their names."""


def read(rec):
    tr = rec["trace"]
    if not tr or rec["chips"] < 2 or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr.get("collective_s", 0.0) / tr["busy_s"]
