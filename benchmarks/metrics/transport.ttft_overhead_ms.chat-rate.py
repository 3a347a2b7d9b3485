"""What the transports add to time to first token: the client's mean (send to
first token) minus the engine's mean (submit to first put, the sum and count
of app_tpu_ttft_duration between the window's ends), over the requests whose
first token fell inside the window."""


def read(ctx):
    n = ctx.prom_close.get("app_tpu_ttft_duration_count", 0.0) \
        - ctx.prom_open.get("app_tpu_ttft_duration_count", 0.0)
    client = [s["first"] - s["sent"] for s in ctx.samples
              if "first" in s and 0.0 <= s["first"] < ctx.seconds]
    if n <= 0 or not client:
        return None
    engine = (ctx.prom_close["app_tpu_ttft_duration_sum"]
              - ctx.prom_open.get("app_tpu_ttft_duration_sum", 0.0)) / n
    return (sum(client) / len(client) - engine) * 1e3
