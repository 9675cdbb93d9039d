"""Double-DQN with experience replay on CartPole (reference rl4j-examples
`Cartpole.java` — QLearningDiscreteDense)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

from deeplearning4j_tpu.rl import (CartPole, QLearningConfiguration,
                                   QLearningDiscrete)


def main():
    env = CartPole(seed=0)
    cfg = QLearningConfiguration(
        seed=1, max_step=6_000, batch_size=64, target_update=250,
        update_start=500, gamma=0.99, eps_min=0.05, anneal_steps=3_000,
        replay_size=10_000)
    ql = QLearningDiscrete(env, cfg)
    rewards = ql.train()
    print(f"episodes: {len(rewards)}, "
          f"last-5 mean reward: {sum(rewards[-5:]) / 5:.1f}")

    policy = ql.get_policy()
    ret = policy.play(CartPole(seed=42))
    print(f"greedy policy return: {ret:.0f}")


if __name__ == "__main__":
    main()
