"""Host time of the two plan lookups (dispatch and combine) per exchange,
in microseconds: the harness's own spans around
``PlannerService.plan_record``, averaged over every exchange of the
measured window."""


def read(ctx):
    spans = ctx.layer.get("plan_s")
    if not spans:
        return None
    return 1e6 * sum(spans) / len(spans)
