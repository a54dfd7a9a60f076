"""A throw-away arrival process for the rehearsal: one request every
``gap_s`` seconds. It exists to show that an arrival process is a new file."""


def plan(params, seconds):
    return {"block": max(1, int(seconds / float(params["gap_s"]))), "blocks": 1}


async def run(load):
    for i in range(plan(load.params, load.seconds)["block"]):
        due = i * float(load.params["gap_s"])
        await load.sleep_until(due)
        load.send(load.take(), due)
