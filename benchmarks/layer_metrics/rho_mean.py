"""Mean importance ratio pi / mu of the window's last row
(``policy/rho_mean``, which IMPALA's ``learn`` reports): 1.0 to rounding
while the fused path learns from the parameters that collected, so a
change that makes the data stale shows here. None where the program
reports no such counter."""

NAME = "rho_mean"


def read(run):
    if not run.window:
        return None
    value = run.window[-1].row.get("policy/rho_mean")
    return None if value is None else float(value)
