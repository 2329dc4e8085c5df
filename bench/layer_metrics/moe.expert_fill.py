"""The share of the expert GEMMs' rows that held routed picks, in percent,
over the window: the program's counters ``moe_routed_rows`` (the active
tokens' picks that landed on the held experts) over ``moe_expert_rows``
(the rows the expert GEMMs ran: held experts x buffer rows x MoE layers,
per tick). A program without the counters gives None."""


def read(run):
    before, after = run.window.counters["before"], run.window.counters["after"]
    if "moe_expert_rows" not in after:
        return None
    rows = after["moe_expert_rows"] - before["moe_expert_rows"]
    if not rows:
        return None
    return 100.0 * (after["moe_routed_rows"] - before["moe_routed_rows"]) \
        / rows
