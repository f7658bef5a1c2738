"""Deploy and plan: the program's own deploy report (deploy_seconds)."""


def read(run):
    return run.deploy_report.get("deploy_seconds")
