"""Elastic launch CLI: python -m edl_tpu.controller.launch <args> script.py

Reference parity: edl/collective/launch.py:32-59 (parse → JobEnv → store →
skip-if-SUCCEED → Pod.from_env → Launcher.init/launch).
"""

import sys

from edl_tpu.controller import constants, status, train_process
from edl_tpu.controller.args import parse_args
from edl_tpu.controller.env import JobEnv
from edl_tpu.controller.launcher import Launcher
from edl_tpu.controller.pod import Pod
from edl_tpu.coordination.client import CoordClient
from edl_tpu.utils.logger import logger


def main(argv=None):
    args = parse_args(argv)
    job_env = JobEnv(args)
    coord = CoordClient(job_env.store_endpoints, root=job_env.job_id)

    job_status = status.load_job_status(coord)
    if job_status == status.Status.SUCCEED:
        logger.info("job %s already SUCCEED; nothing to do", job_env.job_id)
        return 0
    if job_status == status.Status.FAILED:
        # a FAILED verdict and its stale cluster map would deadlock any new
        # launcher (the generator refuses to run under a terminal status);
        # a fresh launch means the operator wants a retry — reset control
        # state (training state/checkpoints are untouched)
        logger.warning("job %s previously FAILED; resetting control state "
                       "for retry", job_env.job_id)
        for service in (constants.SERVICE_JOB_STATUS, constants.SERVICE_CLUSTER,
                        constants.SERVICE_JOB_FLAG, constants.SERVICE_POD_STATUS,
                        constants.SERVICE_TRAIN_STATUS):
            coord._call("store_delete_prefix", coord.service_prefix(service))

    pod = Pod.from_env(job_env)
    train_process.check_chip_ownership(pod)
    launcher = Launcher(job_env, pod, coord, args.training_script,
                        args.training_script_args).init()
    ok = launcher.launch()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
