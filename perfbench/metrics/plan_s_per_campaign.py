"""Host seconds in ``campaign.plan_schedule`` per campaign of the window
(selection and allocation of every round, on the host clock)."""


def read(ctx):
    plan = ctx["host"]["plan"]
    return sum(plan) / len(plan) if plan else None
